"""Share of its roofline that the Pallas flash-decode kernel reaches in the
trace: the least time the chip could take for the decode steps traced,
max(FLOPs / peak FLOP/s, bytes / peak bytes/s), over the kernel's device
time.  FLOPs and bytes come from ``flops.decode_attention`` at each
slot's valid cache length, not the padded width; the kernel is the
Pallas custom call (``tpu_custom_call``) inside the ``_decode_fn`` step."""
from chipbench import flops

KERNEL, STEP = r"tpu_custom_call", "_decode_fn"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.op_seconds(KERNEL, STEP)
    lengths = [n for t, lens in run.steps if run.tracer.covers(t)
               for n in lens]
    if not seconds or not lengths:
        return None
    f = b = 0.0
    for n in lengths:
        df, db = flops.decode_attention(run.shape, n)
        f, b = f + df, b + db
    least = max(f / run.peaks["flops"], b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
