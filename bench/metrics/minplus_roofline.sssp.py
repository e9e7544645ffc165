"""Share of the HBM roofline that the min-plus pull reaches in the
window: the bytes its pulled queue slots need (:func:`minplus_bytes`,
summed over ``sssp.pull_slots``) over the device seconds of the
``sssp.expand`` stage times the chip's HBM bandwidth
(``bench/peaks.json``).

The stage is the kernel ``bvss_spmm_minplus`` and the gathers that bring
its operands (plane rows, mask rows, column distances) out of HBM.  The
compiler places those gathered operands in on-chip memory, so the kernel
alone reads none of them from HBM and its own time would leave that part
of the work out (it read 120% of the roofline on a v5e).  The pull is
bound by bytes: per slot it reads a 4 KiB plane tile for σ·τ additions
and minima."""
from bench.chip import peaks
from bench.program_trace import count, tracer

SIGMA = 8       # the configuration's prepare options (its ``assumed``)
SOURCES = 1     # one source per call (its ``assumed``)


def minplus_bytes(slots: int, sigma: int = SIGMA,
                  sources: int = SOURCES) -> int:
    """Logical bytes of ``slots`` pulled queue slots: per slot its mask row
    (32 u32), its plane rows (σ x τ f32), the σ column distances of each
    source in, and the τ relaxed rows of each source out."""
    tau = (32 // sigma) * 32
    return slots * 4 * (32 + sigma * tau + sigma * sources + tau * sources)


def read(run):
    t = tracer()
    if t is None or run.trace is None or not run.trace.n_ops:
        return None
    stages = t.stages()
    seconds = sum(sec for op, sec in run.trace.op_seconds.items()
                  if stages.get(op.split(" ", 1)[0]) == "sssp.expand")
    slots = count(run, "sssp.pull_slots")
    peak = peaks().get(run.devices[0].device_kind, {}).get("hbm_bytes_per_s")
    if not seconds or not slots or not peak:
        return None
    return 100.0 * minplus_bytes(slots) / (seconds * peak)
