"""Pallas launches of one training step: ``ops.count_launches`` on the
trainer's unjitted step (``Trainer.raw_step_fn``), traced abstractly
once after the window on the shapes of the cell's state and batch."""


def read(ctx):
    return ctx.get("matfn_launches")
