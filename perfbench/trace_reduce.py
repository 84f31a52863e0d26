"""Reduce a ``jax.profiler`` trace to the device numbers the benchmark
reports: busy and idle time, kernel device time, all-reduce time, the
device operations that took most time, and idle time by what the host
was doing.

Device planes are ``/device:TPU:<i>``; their operations are the events of
the ``XLA Ops`` line.  A kernel is found by a pattern in the event's name
or in one of its string stats: on a TPU the event's name is the HLO
instruction, which takes the name of the jitted function that wraps the
``pallas_call`` (``%_fused.6 = f32[...] custom-call(...)``), and a kernel
body's own name (``_fused_kernel``, ``_nystrom_kernel``) is matched too.
Operations are listed under their HLO name without the shapes.  The window
is the host span ``bench.window`` that the harness writes around the
measured window; busy time is the union of operation intervals inside it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
from dataclasses import dataclass, field

KERNELS = {
    "fused_rbf": ("%_fused.", "_fused_kernel"),
    "fused_nystrom": ("%_nystrom.", "_nystrom_kernel"),
}
ALL_REDUCE = ("all-reduce", "all_reduce", "allreduce")
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


@dataclass
class Reduced:
    window_s: float
    busy_s: float                        # averaged over the devices
    kernel_s: dict = field(default_factory=dict)   # per device, averaged
    allreduce_s: float = 0.0             # device 0
    top_ops: list = field(default_factory=list)
    idle_gaps: list = field(default_factory=list)

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self) -> dict:
        return {"device_ops": [list(x) for x in self.top_ops[:10]],
                "idle_gaps": [list(x) for x in self.idle_gaps[:10]]}


def _device_planes(pd, n_devices: int):
    found = []
    for pl in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", pl.name)
        if m:
            found.append((int(m.group(1)), pl))
    found.sort(key=lambda t: t[0])
    return [pl for _, pl in found[:n_devices]]


def _events(plane, line_name):
    for ln in plane.lines:
        if ln.name == line_name:
            return [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                     [v for _, v in e.stats if isinstance(v, str)])
                    for e in ln.events]
    return []


def _host_spans(pd):
    out = []
    for pl in pd.planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name and not e.name.startswith("$"):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    return out


def _union(intervals, lo, hi):
    merged = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _kernel_of(name, stats):
    for label, pats in KERNELS.items():
        if any(p in name or any(p in s for s in stats) for p in pats):
            return label
    return None


def _host_activity(spans, starts, longest, g0, g1) -> str:
    """The host span that covers most of the gap [g0, g1]; of equal
    covers, the innermost (shortest)."""
    best, best_len, name = 0, None, "host: no span"
    i = bisect.bisect_left(starts, g1)
    while i > 0:
        i -= 1
        a, b, n = spans[i]
        if a < g0 - longest:
            break
        ov = min(b, g1) - max(a, g0)
        if ov > best or (ov == best and ov > 0 and b - a < best_len):
            best, best_len, name = ov, b - a, n
    return name


def reduce(pd, n_devices: int) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``."""
    planes = _device_planes(pd, n_devices)
    if not planes:
        raise ValueError("the trace has no TPU device plane")
    per_dev = [_events(pl, OPS_LINE) for pl in planes]
    host = _host_spans(pd)
    win = [(a, b) for a, b, name in host if name == WINDOW]
    if win:
        lo, hi = min(a for a, _ in win), max(b for _, b in win)
    else:
        lo = min(a for evs in per_dev for a, *_ in evs)
        hi = max(b for evs in per_dev for _, b, *_ in evs)
    busy, kernel, by_op = [], {}, {}
    for evs in per_dev:
        merged = _union([(a, b) for a, b, *_ in evs], lo, hi)
        busy.append(sum(b - a for a, b in merged))
        for a, b, name, stats in evs:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            label = _kernel_of(name, stats)
            if label:
                kernel[label] = kernel.get(label, 0.0) + (b - a)
            key = label or name.split(" = ")[0]
            by_op[key] = by_op.get(key, 0.0) + (b - a)
    nd = len(per_dev)
    allreduce = sum(min(b, hi) - max(a, lo) for a, b, name, _ in per_dev[0]
                    if any(p in name.lower() for p in ALL_REDUCE)
                    and min(b, hi) > max(a, lo))
    gaps: dict = {}
    merged0 = _union([(a, b) for a, b, *_ in per_dev[0]], lo, hi)
    edges = [lo] + [x for ab in merged0 for x in ab] + [hi]
    spans = sorted((a, b, n) for a, b, n in host if n != WINDOW)
    starts = [a for a, _, _ in spans]
    longest = max((b - a for a, b, _ in spans), default=0)
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        gaps_name = _host_activity(spans, starts, longest, g0, g1)
        gaps[gaps_name] = gaps.get(gaps_name, 0.0) + (g1 - g0)
    ns = 1e-9
    return Reduced(
        window_s=(hi - lo) * ns, busy_s=sum(busy) / nd * ns,
        kernel_s={k: v / nd * ns for k, v in kernel.items()},
        allreduce_s=allreduce * ns,
        top_ops=sorted(((k, v / nd * ns) for k, v in by_op.items()),
                       key=lambda t: -t[1]),
        idle_gaps=sorted(((k, v * ns) for k, v in gaps.items()),
                         key=lambda t: -t[1]))


def load(path: str):
    """A ``ProfileData`` from an ``.xplane.pb`` file, gzipped or not."""
    import gzip

    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    if path.endswith(".gz"):
        data = gzip.decompress(data)
    return ProfileData.from_serialized_xspace(data)


def reduce_dir(trace_dir: str, n_devices: int) -> Reduced:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(load(files[-1]), n_devices)


def remove(trace_dir: str) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)
