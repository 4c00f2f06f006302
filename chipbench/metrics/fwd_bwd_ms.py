"""Device time of one standalone call of the model's loss and gradient
(``models/transformer.py``) on one batch of the cell."""


def read(ctx):
    return ctx.get("fwd_bwd_ms")
