"""``fused_embed``'s share of its roofline: the least time the chip could
take for the rows the kernel was given in the window (the larger of
operations over peak FLOP/s and bytes over HBM bandwidth, call by call)
over the kernel's time in the device trace, in percent. The bound that
holds is written to the run's notes."""
from harness import trace as tr

KERNEL = "fused_embed"


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.lo_ns, ctx.trace.hi_ns
    ops = [o for o in tr.kernel_ops(ctx.trace, KERNEL)
           if o.end_ns > lo and o.start_ns < hi]
    t_kernel = tr.clipped_s(ops, lo, hi)
    calls = ctx.spans.trunk_rows
    if t_kernel <= 0 or not calls:
        return None
    work = ctx.trunk.fused_embed_work
    d, k = ctx.config["in_dim"], ctx.config["width"]
    peak_f, peak_b = ctx.peaks["flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    least = t_f = t_b = 0.0
    for n in calls:
        f, b = work(n, d, k)
        least += max(f / peak_f, b / peak_b)
        t_f += f / peak_f
        t_b += b / peak_b
    bound = "memory" if t_b >= t_f else "compute"
    inside = tr.covered_share(ops, ctx.trace.spans.get("run_infer", []))
    ctx.notes.append(
        f"{KERNEL}_roofline: {len(ops)} kernel ops, {t_kernel:.6f} s in the "
        f"trace, {100 * inside:.1f}% of it inside run_infer spans; "
        f"{len(calls)} calls, {sum(calls)} rows; least time {least:.6f} s, "
        f"{bound}-bound (FLOP time {t_f:.6f} s, byte time {t_b:.6f} s)")
    return 100.0 * least / t_kernel
