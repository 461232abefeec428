"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The traced window is the host span ``chipbench.window`` that the harness
opens around it. On each device plane (``/device:TPU:<n>``) the line of
XLA operations gives the busy time (the union of the operations' intervals
inside the window), each kernel's device time (operations whose HLO
instruction name starts with one of the kernel's names in ``KERNELS``) and
each XLA program's device time (the line of XLA modules). Idle gaps are
attributed to the innermost host span that covers their middle.

An event's name on that line is the HLO instruction's text,
``%<name>.<n> = <shape> <opcode>(<operands>) ...``; only the part before
`` = `` names the operation, since the operands name others. Events nest:
a ``while`` spans the operations of its body, so an operation's time in the
breakdown is its own, less the time of the operations inside it.
"""
from __future__ import annotations

import collections

# kernel -> prefixes of its device operations' instruction names: the
# Pallas calls are named after the functions that make them, as a TPU v5e
# trace shows (``%flash_attention_pallas_fwd.17``, ``%ssd_chunk_pallas.15``,
# ``%ssd_chunk_pallas_bwd.9``); the backward's dq and dk/dv calls both carry
# ``flash_attention_pallas_bwd``
KERNELS = {
    "flash_attention": ("flash_attention_pallas_fwd",
                        "flash_attention_pallas_bwd"),
    "ssd": ("ssd_chunk_pallas",),
}
LABEL = 120         # characters of an instruction's text kept in breakdown
WINDOW = "chipbench.window"
TOP = 10


def _union(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _gaps(intervals, lo, hi):
    out, end = [], lo
    for s, e in sorted(intervals):
        if s > end:
            out.append((end, s))
        end = max(end, e)
    if hi > end:
        out.append((end, hi))
    return out


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")]


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def op_name(text: str) -> str:
    """The instruction's own name, ``%fusion.195 = ...`` -> ``fusion.195``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def kernel_of(text: str) -> str | None:
    name = op_name(text)
    for kernel, prefixes in KERNELS.items():
        if any(name.startswith(p) for p in prefixes):
            return kernel
    return None


def _own_times(intervals):
    """(own time, label) of nested (start, end, label) intervals: each
    interval's length less the part of it that the intervals directly
    inside it cover."""
    out, stack = [], []
    for s, e, label in sorted(intervals, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, lab, own = stack.pop()
            out.append((own, lab))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, label, e - s])
    out += [(own, lab) for _, lab, own in stack]
    return out


def idle_share(reduced) -> float | None:
    """Share (%) of the traced window in which no operation ran on the
    device, averaged over the chips used; None without a trace."""
    if not reduced or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def reduce(path, n_devices: int) -> dict:
    """The reduction of the ``.xplane.pb`` file at ``path``."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)), n_devices)


def reduce_profile(pd, n_devices: int) -> dict:
    """busy_s and window_s (averaged over the devices), kernel_s and
    module_s (device seconds summed over the devices), and the breakdown of
    the top device operations and the longest idle gaps. ``pd`` has the
    shape of ``jax.profiler.ProfileData``: planes of lines of events."""
    host = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for p in pd.planes if not p.name.startswith("/device:")
            for line in p.lines for e in line.events]
    spans = [(s, e) for s, e, n in host if n == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = spans[0]
    planes = _device_planes(pd)[:n_devices]
    busy, kernel_s = 0.0, collections.Counter()
    module_s = collections.Counter()
    op_s, gaps = collections.Counter(), []
    for plane in planes:
        ops = _line(plane, "XLA Ops")
        intervals = []
        for e in (ops.events if ops is not None else ()):
            s, t = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
            if t <= s:
                continue
            intervals.append((s, t, e.name))
            kernel = kernel_of(e.name)
            if kernel:
                kernel_s[kernel] += (t - s) / 1e9
        for own, text in _own_times(intervals):
            op_s[text[:LABEL]] += own / 1e9
        busy += _union([(s, t) for s, t, _ in intervals]) / 1e9
        gaps += _gaps([(s, t) for s, t, _ in intervals], lo, hi)
        mods = _line(plane, "XLA Modules")
        for e in (mods.events if mods is not None else ()):
            s, t = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
            if t > s:
                module_s[e.name.split("(")[0]] += (t - s) / 1e9
    n = max(len(planes), 1)
    gaps.sort(key=lambda g: g[0] - g[1])

    def doing(g):
        mid = (g[0] + g[1]) / 2
        cover = [(e - s, name) for s, e, name in host
                 if s <= mid <= e and name != WINDOW]
        return min(cover)[1] if cover else "no host span"

    idle = collections.Counter()
    for g in gaps[:100]:
        idle[doing(g)] += (g[1] - g[0]) / 1e9
    return {
        "busy_s": busy / n, "window_s": (hi - lo) / 1e9,
        "kernel_s": dict(kernel_s), "module_s": dict(module_s),
        "devices": len(planes),
        "breakdown": {
            "device_ops": [[k, v] for k, v in op_s.most_common(TOP)],
            "idle_gaps": [[k, v] for k, v in idle.most_common(TOP)],
        },
    }
