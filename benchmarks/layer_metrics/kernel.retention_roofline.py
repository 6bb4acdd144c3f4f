"""Kernels, for a decoder with power-retention layers (`model_type`
brumby): the least time the chip could take for the step kernel's work
in the slice — for every (row, step) pair the plain decode segments
advanced, in every layer, the state read once and written once at
`state_rows_min` x (head_dim + 1) x 4 bytes a kv head, plus the row's q,
k, v, gate and y (harness/retention_cost.py) — over the device time of
the kernel the program names `retention_step`. Memory-bound by two
orders. A share over 100 says the floor counts too much, the state is
narrower than the file states, or the time leaves out work: it is an
error, not a value. So is a slice whose plain segments advanced rows
while no kernel of that name ran: the program then served the step
through `jax.numpy` (`describe()["declines"]["retention_step"]`), which
`harness/server.degraded_paths` does not watch for this kind."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import kernel_cost, loopspans, retention_cost  # noqa: E402


def read(ctx):
    trace, sl, config = ctx["trace"], ctx["slice"], ctx["config"]
    if not trace or not sl or not retention_cost.is_retention(config):
        return None
    seconds = retention_cost.retention_seconds(
        trace["op_seconds"], config, retention_cost.KERNEL)
    spans = loopspans.slice_spans(ctx)
    if spans is None:
        return None
    row_steps = sum(r["attrs"]["decode_tokens"] for r in spans
                    if r["rung"] == "segment"
                    and r.get("attrs", {}).get("kind") == "plain")
    if not row_steps:
        return None
    if seconds <= 0:
        raise RuntimeError(
            f"kernel.retention_roofline: the slice's plain segments "
            f"advanced {row_steps} rows and no `{retention_cost.KERNEL}` "
            "kernel ran on the device: the decode step was served "
            "through jax.numpy (describe()['declines'])")
    work = retention_cost.step_kernel_floor(config, row_steps)
    share = 100.0 * kernel_cost.least_seconds(
        work, ctx["peaks"])["seconds"] / seconds
    if share > 100.0:
        raise RuntimeError(
            f"kernel.retention_roofline reads {share:.1f} %: the floor "
            "of harness/retention_cost.py counts too much, the state is "
            "narrower than the configuration states, or the step "
            "kernel's time leaves out work")
    return share
