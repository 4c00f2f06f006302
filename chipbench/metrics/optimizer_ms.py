"""Device time of one standalone call of the optimizer's update
(``optim/muon.py``, ``optim/bucketing.py``) on the cell's state and one
step's gradients."""


def read(ctx):
    return ctx.get("optimizer_ms")
