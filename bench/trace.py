"""The one reduction from a profiler trace (``.xplane.pb``) to numbers.

Reads the trace with ``jax.profiler.ProfileData`` only.  Device planes
are those named ``/device:TPU:<n>``; on each, the ``XLA Modules`` line
holds one event per program execution and the ``XLA Ops`` line one per
operation.  Host planes hold the spans the drivers open with
``jax.profiler.TraceAnnotation``.  All share one clock.

``reduce`` returns:
  * ``busy_s`` — the union of operation intervals, averaged over the
    device planes; ``window_s`` — the traced window it is measured in;
  * ``modules`` — device seconds per program (``jit_`` prefix and
    fingerprint dropped: ``decode_horizon_step``);
  * ``kernels`` — device seconds of each Pallas kernel, per program it
    ran inside (``{"paged_attention": {"decode_horizon_step": s}}``),
    and ``kernel_calls`` — how many calls;
  * ``breakdown`` — the ten operations (by program) that took most time,
    and the idle gaps summed by the innermost host span the host was in.
"""
from __future__ import annotations

import bisect
import os
import re

KERNELS = ("paged_attention_q8", "paged_attention", "topk_scan_q",
           "topk_scan", "scan_filter_reduce_q", "scan_filter_reduce")
#: host spans idle gaps are attributed to (innermost wins)
SPAN_PREFIXES = ("bench.", "scheduler.", "server.", "driver.")
#: control-flow operations whose bodies' operations are traced too
CONTAINERS = ("while", "conditional", "call")
#: gaps shorter than this sit between the operations of one program
GAP_NS = 10_000

_FINGERPRINT = re.compile(r"\(\d+\)$")
_NUMBERS = re.compile(r"\.\d+")


def find_xplane(trace_dir: str) -> str:
    for root, _, names in os.walk(trace_dir):
        for n in names:
            if n.endswith(".xplane.pb"):
                return os.path.join(root, n)
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")


def module_name(event_name: str) -> str:
    """``jit_decode_horizon_step(1234)`` -> ``decode_horizon_step``."""
    n = _FINGERPRINT.sub("", event_name.strip())
    return n[4:] if n.startswith("jit_") else n


def op_name(event_name: str) -> str:
    """An operation event's instruction name: the TPU trace names an op
    by its HLO text, ``%paged_attention.7 = bf16[...] custom-call(...)``
    -> ``paged_attention.7``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def kernel_of(name: str, stats) -> str | None:
    """The Pallas kernel an operation event is, if any: by its
    instruction name, or by a stat that names it."""
    name = op_name(name)
    for k in KERNELS:
        if name == k or name.startswith(k + "."):
            return k
    for _, v in stats:
        if isinstance(v, str):
            for k in KERNELS:
                if k in v:
                    return k
    return None


def union_length(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _lines(plane):
    return {line.name: list(line.events) for line in plane.lines}


def reduce(path: str, t_start: float | None = None,
           t_stop: float | None = None) -> dict:
    """Reduce the trace at ``path``.  ``t_start``/``t_stop`` are the
    host monotonic times the trace was started and stopped at; their
    difference is the window (else the trace's own extent is)."""
    from jax.profiler import ProfileData
    return reduce_data(ProfileData.from_file(path), t_start, t_stop)


def reduce_data(pd, t_start: float | None = None,
                t_stop: float | None = None) -> dict:
    """:func:`reduce` of a loaded ``ProfileData``."""
    devices, host_spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(_lines(plane))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        host_spans.append((e.start_ns, e.end_ns, e.name))
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    modules, kernels, calls, ops_time = {}, {}, {}, {}
    kind = {}                          # op name -> kernel (or None)
    busy_ns, all_busy = [], []
    first = last = None
    for lines in devices:
        mods = [(e.start_ns, e.end_ns, module_name(e.name))
                for e in lines.get("XLA Modules", [])]
        for s, e, m in mods:
            modules[m] = modules.get(m, 0.0) + (e - s) / 1e9
        mods.sort()
        ops = lines.get("XLA Ops", [])
        intervals = []
        mi = 0
        for ev in sorted(ops, key=lambda x: x.start_ns):
            s, e = ev.start_ns, ev.end_ns
            intervals.append((s, e))
            while mi + 1 < len(mods) and mods[mi + 1][0] <= s:
                mi += 1
            mod = mods[mi][2] if mods and mods[mi][0] <= s <= mods[mi][1] \
                else "?"
            if ev.name not in kind:
                kind[ev.name] = kernel_of(ev.name, ev.stats)
            k = kind[ev.name]
            if k is not None:
                kernels.setdefault(k, {})
                kernels[k][mod] = kernels[k].get(mod, 0.0) + (e - s) / 1e9
                calls[k] = calls.get(k, 0) + 1
            short = _NUMBERS.sub("", op_name(ev.name))
            if short not in CONTAINERS:      # their bodies are listed
                key = f"{mod}/{k or short}"
                ops_time[key] = ops_time.get(key, 0.0) + (e - s) / 1e9
        if not intervals:
            intervals = [(s, e) for s, e, _ in mods]
        busy_ns.append(union_length(intervals))
        all_busy += intervals
        if intervals:
            lo = min(s for s, _ in intervals)
            hi = max(e for _, e in intervals)
            first = lo if first is None else min(first, lo)
            last = hi if last is None else max(last, hi)
    if t_start is not None and t_stop is not None:
        window_s = t_stop - t_start
    else:
        window_s = ((last - first) / 1e9) if first is not None else 0.0
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    gaps = {}
    merged = _merged(all_busy)
    host_spans.sort(key=lambda h: (h[0], -h[1]))    # outer span first
    starts = [h[0] for h in host_spans]
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        if s1 - e0 < GAP_NS:
            name = f"between ops (< {GAP_NS // 1000} us)"
        else:
            # spans nest: the innermost one covering the gap is the
            # latest-starting one that has not ended
            mid = (e0 + s1) / 2
            i = bisect.bisect_right(starts, mid) - 1
            while i >= 0 and host_spans[i][1] < mid:
                i -= 1
            name = host_spans[i][2] if i >= 0 else "no host span"
        gaps[name] = gaps.get(name, 0.0) + (s1 - e0) / 1e9

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:10]]

    return {"busy_s": busy_s, "window_s": window_s, "modules": modules,
            "kernels": kernels, "kernel_calls": calls,
            "n_devices": len(devices),
            "breakdown": {"device_ops": top(ops_time),
                          "idle_gaps": top(gaps)}}
