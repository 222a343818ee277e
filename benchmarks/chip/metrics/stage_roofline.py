"""Roofline share of the stage programs: the least time the chip could take
for the window's stage forward and backward work -- the larger of its FLOPs
over the peak FLOP/s and its bytes over the peak bandwidth, both counted from
shapes -- over the device time of those programs in the trace, in percent.

The stage programs are the ``jit_fwd_fn`` and ``jit_bwd_fn`` modules.  Bytes
count each micro-batch's bf16 parameter reads in the forward and the
backward pass (the embedding's gathered rows only), its fp32 gradient
writes and its boundary activations; FLOPs are three forward passes."""

PROGRAMS = ("jit_fwd_fn", "jit_bwd_fn")


def read(run):
    if run.device is None or run.peaks is None:
        return None
    busy = sum(run.device.program_s.get(p, 0.0) for p in PROGRAMS)
    if busy <= 0.0:
        return None
    flops_s = run.stage_flops_per_step / run.peaks["bf16_flops_per_s"]
    bytes_s = run.stage_bytes_per_step / run.peaks["hbm_bytes_per_s"]
    run.log(f"stage_roofline: bound by "
            f"{'compute' if flops_s >= bytes_s else 'memory'} "
            f"({flops_s * 1e3:.2f} ms of FLOPs, {bytes_s * 1e3:.2f} ms of "
            f"bytes per step; {busy / run.window_steps * 1e3:.2f} ms on the "
            f"device)")
    return 100.0 * max(flops_s, bytes_s) * run.window_steps / busy
