"""Pallas launches of one training step: ``ops.count_launches`` on the
trainer's unjitted step (``Trainer.raw_step_fn``) with the cell's state
and batch, traced abstractly once after the window."""


def read(ctx):
    return ctx.get("matfn_launches")
