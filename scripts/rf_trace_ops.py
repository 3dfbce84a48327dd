"""A forest's growth, operation by operation, from a kept trace.

    python3 chipbench/run.py --workload rf_dbx.job --seed N --seconds 51 --trace 1 --keep-trace chiprun_out/rf.xplane.pb
    python3 scripts/rf_trace_ops.py chiprun_out/rf.xplane.pb [table.tsv]

Reads the device's ``XLA Ops`` inside the traced ``RandomForestClassifier.fit``
(``chipbench/trace_reduce.py``), takes nested operations out of their loops
(self time), and names every operation by its own HLO line, which the trace
carries: a fusion's number changes with every program, its output shape and
operands do not. Prints seconds and milliseconds a tree and level by what a
level does (PERF.md section 5's table, PR 36); the optional tsv holds every
operation: seconds, events, name, HLO line. Not a benchmark metric: a reader's
aid for the next change to ``ops/tree_kernels.py``.
"""
import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import trace_reduce as tr  # noqa: E402


def kind(name: str, hlo: str) -> str:
    if name.startswith("rf_hist"):
        return "2 histogram kernel (rf_hist_sel_pass / rf_hist_pass)"
    m = re.search(r"= \(?([a-z0-9]+)\[([0-9,]*)\]", hlo)
    if not m:
        return "9 the rest"
    dt, d = m.group(1), [int(x) for x in m.group(2).split(",") if x]
    rows = lambda x: x >= 400_000 or x == 16384  # noqa: E731  a level's padded rows, or a chunk of them
    if any(k in hlo for k in ("f32[500000,3000]", "f32[131072", "f32[65536,3000]")):
        return "0 not the growth (crossing, sketch, binize)"
    if dt in ("u8", "bf16") and len(d) == 2 and d[1] >= 1024 and rows(d[0]):
        return "1 whole-row gather of the bins (the wide selection: with its bf16 cast, forest.wide_rows)"
    if dt == "bf16" and d == [16, 16384]:
        return "5b the statistics' exact three-way bf16 split (forest.wide_rows)"
    if dt == "f32" and len(d) >= 3 and d[-1] >= 16384:
        return "3b the wide kernel's per-node sums: zero fill, the three parts added, slots back in order"
    if dt == "f32" and len(d) == 2 and d[1] == 16384:
        if name.startswith("reshape"):
            return "4 partials relaid (reshape)"
        if name.startswith(("fusion", "broadcast")):
            return "3 partials' per-node sums (segment_sum / scatter-add, its zero fill)"
    if dt == "u8" and len(d) == 1 and rows(d[0]):
        return "6 routing: the split feature's bin a row (element gather)"
    if dt in ("pred", "s32") and d == [500000] and name.startswith("fusion"):
        return "7 routing: the tables a row and the move (gathers or a scatter)"
    if (dt == "f32" and ((len(d) == 2 and d[1] == 2) or len(d) == 1) and d and rows(d[0])) or (
        dt == "s32" and len(d) == 1 and rows(d[0]) and name.startswith("fusion")
    ):
        return "5 ids and weights a sorted position (perm[src], sw[rows])"
    if name.startswith("sort"):
        return "8 sort by node / feature draw (a sort of uniforms)"
    return "9 the rest"


def main(path: str, out: str | None) -> None:
    from jax.profiler import ProfileData

    ops, host = tr.read_planes(path)
    ops = next(iter(v for v in ops.values() if v))
    fit = host.get("RandomForestClassifier.fit") or host.get("RandomForestRegressor.fit") or []
    if fit:
        ops = [o for o in ops if fit[0][0] <= o[1] <= fit[0][1]]
    hlo = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    for e in line.events:
                        hlo.setdefault(tr.short(e.name), e.name[:260])
    events = collections.Counter(name for name, _, _ in ops)
    kernel_events = sum(c for name, c in events.items() if name.startswith("rf_hist"))
    table = sorted(((ns * 1e-9, name) for name, ns in tr.self_times(ops).items()), reverse=True)
    by = collections.defaultdict(float)
    for secs, name in table:
        by[kind(name, hlo.get(name, ""))] += secs
    print(f"{len(table)} operations, {sum(by.values()):.3f} s of self time inside the fit; {kernel_events} kernel events")
    for k in sorted(by):
        print(f"  {by[k]:8.3f} s  {k}")
    if out:
        with open(out, "w") as f:
            for secs, name in table:
                f.write(f"{secs:.6f}\t{events[name]}\t{name}\t{hlo.get(name, '')}\n")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else None)
