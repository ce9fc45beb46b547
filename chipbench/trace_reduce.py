"""From a profiler trace to numbers: the one reduction every PR shares.

A trace is held as plain data, ``{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}]}``: :func:`load_xplane`
reads that from the ``.xplane.pb`` the JAX profiler writes (through
``jax.profiler.ProfileData``, nothing else), and the recorded fixture under
``chipbench/fixtures/`` is the same structure as JSON, cut from a real run.

What the planes and lines are called was read off a trace of a TPU v5e by
hand (PERF.md, Findings): each chip is a plane ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per executed HLO operation, its line ``XLA
Modules`` one per executed program; the host is ``/host:CPU`` with one line
per thread, where ``jax.profiler.TraceAnnotation`` spans appear by name.
"""

from __future__ import annotations

import gzip
import json
import re
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]  # [start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"

#: a device event's name is the whole HLO instruction, ``%name.N = type
#: opcode(operands), attributes``
_HLO = re.compile(r"^%(?P<name>[^ ]+) = (?P<type>.*?) (?P<op>[a-z][a-z0-9\-]*)\((?P<rest>.*)$", re.S)
#: HLO collectives, by opcode (``-start``/``-done`` halves included)
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|collective-broadcast)"
)
MOSAIC = 'custom_call_target="tpu_custom_call"'
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def parse_op(name: str) -> Dict[str, str]:
    """``{"name", "type", "op", "rest"}`` of an HLO instruction's text; a
    name that is not one (a host event) comes back as its own ``name``."""
    m = _HLO.match(name)
    if not m:
        return {"name": name, "type": "", "op": "", "rest": ""}
    return m.groupdict()


def flash_kernel(name: str) -> Optional[str]:
    """Which of ``adapcc_tpu/ops/flash_attention.py``'s three Mosaic kernels
    an operation is, told by its signature (the custom calls carry the flax
    scope's name, ``%attn.N``, not the kernel function's): the forward
    kernel takes q, k, v; both backward kernels take q, k, v, do, lse, delta,
    and dq gives one array where dkv gives two."""
    if MOSAIC not in name:
        return None
    op = parse_op(name)
    if op["op"] != "custom-call":
        return None
    operands = op["rest"].split("), custom_call_target", 1)[0].count(" %")
    if operands == 3:
        return "flash_fwd"
    if operands == 6:
        return "flash_bwd_dkv" if op["type"].startswith("(") else "flash_bwd_dq"
    return None


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(parse_op(name)["op"]))


def stable_name(name: str) -> str:
    """A name for an operation that survives a recompile: a flash kernel's
    or a collective's own; else the instruction's stem (``fusion.123`` ->
    ``fusion``) with the type it produces, layouts left out."""
    kernel = flash_kernel(name)
    if kernel:
        return kernel
    op = parse_op(name)
    if not op["op"]:
        return name[:80]
    if COLLECTIVE.match(op["op"]):
        return re.sub(r"-(start|done)$", "", op["op"])
    stem = re.sub(r"[.\-_]?\d+$", "", op["name"])
    produced = re.sub(r"\{[^}]*\}", "", op["type"])
    return f"{stem} {produced}"[:80]


def load_xplane(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_json(path: str) -> Dict[str, Any]:
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def find_xplane(trace_dir: str) -> Optional[Path]:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


# --------------------------------------------------------------------------- #
# interval arithmetic
# --------------------------------------------------------------------------- #


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(end - start for start, end in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of the merged intervals ``a`` that no interval of the merged
    ``b`` covers."""
    out: List[Interval] = []
    j = 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


# --------------------------------------------------------------------------- #
# the trace's parts
# --------------------------------------------------------------------------- #


def device_ops(trace: Dict[str, Any]) -> Dict[int, List[List[Any]]]:
    """Per chip, the events of its ``XLA Ops`` line."""
    out: Dict[int, List[List[Any]]] = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                out.setdefault(int(m.group(1)), []).extend(line["events"])
    return out


def host_spans(trace: Dict[str, Any], prefix: str) -> List[List[Any]]:
    """Host events whose name starts with ``prefix`` (the benchmark's own
    ``TraceAnnotation`` spans), from every host thread."""
    out = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            out.extend(e for e in line["events"] if e[0].startswith(prefix))
    return sorted(out, key=lambda e: e[1])


def _ivals(events: Iterable[Sequence[Any]]) -> List[Interval]:
    return [(e[1], e[1] + e[2]) for e in events]


def window_of(ops: Dict[int, List[List[Any]]], spans: List[List[Any]]) -> Interval:
    """The traced window: from the start of the first host span to the end
    of the last device operation (the last step's work)."""
    starts = [e[1] for e in spans] or [e[1] for evs in ops.values() for e in evs]
    ends = [e[1] + e[2] for evs in ops.values() for e in evs]
    return (min(starts), max(ends))


def reduce_trace(trace: Dict[str, Any], span_prefix: str = "chipbench.") -> Dict[str, Any]:
    """Everything the per-layer readers and the result line take from one
    trace, in seconds, averaged over the chips that ran something:

    ``window_s``; ``busy_s`` (union of operation intervals); ``exposed_
    collective_s`` (a collective runs and nothing else does); ``kernel_s``
    (summed duration by flash kernel); ``device_ops`` (top operations by
    summed time, stable names) and ``idle_gaps`` (longest gaps, each named by
    the host span that covered most of it)."""
    ops = {d: evs for d, evs in device_ops(trace).items() if evs}
    if not ops:
        return {"devices": 0}
    spans = host_spans(trace, span_prefix)
    window = window_of(ops, spans)
    n = len(ops)
    busy_s = exposed_s = 0.0
    kernel_s = {k: 0.0 for k in FLASH_KERNELS}
    labels: Dict[str, Tuple[str, Optional[str], bool]] = {}

    def label(name: str):
        if name not in labels:
            labels[name] = (stable_name(name), flash_kernel(name), is_collective(name))
        return labels[name]

    by_name: Dict[str, float] = {}
    gaps: List[Tuple[int, Interval]] = []
    for evs in ops.values():
        busy = clip(union(_ivals(evs)), window)
        busy_s += total(busy) / 1e9 / n
        coll = union(_ivals(e for e in evs if label(e[0])[2]))
        rest = union(_ivals(e for e in evs if not label(e[0])[2]))
        exposed_s += total(clip(subtract(coll, rest), window)) / 1e9 / n
        for name, _, dur in evs:
            stable, kernel, _ = label(name)
            if kernel:
                kernel_s[kernel] += dur / 1e9 / n
            by_name[stable] = by_name.get(stable, 0.0) + dur / 1e9 / n
        gaps.extend((e - s, (s, e)) for s, e in subtract([window], busy))
    span_ivals = [(e[0][len(span_prefix):], (e[1], e[1] + e[2])) for e in spans]
    idle: Dict[str, float] = {}
    for _, gap in sorted(gaps, reverse=True)[:200]:
        cover: Dict[str, int] = {}
        for name, iv in span_ivals:
            got = total(clip([iv], gap))
            if got:
                cover[name] = cover.get(name, 0) + got
        label = max(cover, key=cover.get) if cover else "other"
        if cover and cover[label] * 2 < gap[1] - gap[0]:
            label = "other"
        idle[label] = idle.get(label, 0.0) + (gap[1] - gap[0]) / 1e9 / n
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": n,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": busy_s,
        "exposed_collective_s": exposed_s,
        "kernel_s": kernel_s,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": [[k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:10]],
    }
