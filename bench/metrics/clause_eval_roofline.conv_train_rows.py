"""Kernels: roofline time of the patch clause evaluation of training over its kernels' device time, %."""
import kinds
import readers


def read(ctx):
    if not ctx.get("train_rows"):
        return None
    return readers.roofline(ctx, "clause_eval", kinds.of(
        ctx["config"]).clause_eval(ctx["config"], ctx["train_rows"],
                                   ctx["train_steps"]))
