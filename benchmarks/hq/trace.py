"""From the profiler's trace to plain records, and from records to time.

``extract`` reads the ``.xplane.pb`` a traced run wrote and keeps what the
metrics need, as plain lists with times in nanoseconds on the trace's one
clock:

* ``device_ops``: [name, start, duration, module] of every operation on the
  first device's op line (``XLA Ops``), named by its HLO instruction, with
  the program (``XLA Modules``) it ran in;
* ``host_spans``: [name, start, duration] of the benchmark's own ``hq.``
  annotations on the host;
* ``window``: [start, end] of the ``hq.window`` annotation.

The reductions below work on those records only, so they are checked on a
small recorded trace without a chip.
"""
from __future__ import annotations

import glob
import json
import os

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "hq."


def newest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def _stat(ev, key):
    try:
        for k, v in ev.stats:
            if k == key:
                return v
    except Exception:  # noqa: BLE001 — a stat that will not decode is absent
        return None
    return None


def op_name(text: str) -> str:
    """An op event's name as the trace gives it is its whole HLO line;
    keep the instruction name (``%gather_score_blocks.1``)."""
    return text.split(" = ", 1)[0]


def module_name(text: str) -> str:
    """``jit_search_local_batch(123...)`` -> ``jit_search_local_batch``."""
    return text.split("(", 1)[0]


def _modules_of(ops: list, modules: list) -> list:
    """The module event holding each op (both sorted by start)."""
    out, j = [], 0
    for _, s, _, _ in ops:
        while j + 1 < len(modules) and modules[j + 1][0] <= s:
            j += 1
        m = modules[j] if modules and modules[j][0] <= s <= modules[j][1] \
            else None
        out.append(m[2] if m else "")
    return out


def extract(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans, window = [], [], [], None
    device_planes = sorted((p for p in pd.planes
                            if p.name.startswith(DEVICE_PREFIX)),
                           key=lambda p: p.name)
    for plane in device_planes[:1]:
        for line in plane.lines:
            if line.name == OP_LINE:
                for ev in line.events:
                    ops.append([op_name(ev.name), float(ev.start_ns),
                                float(ev.duration_ns), ""])
            elif line.name == MODULE_LINE:
                for ev in line.events:
                    modules.append((float(ev.start_ns),
                                    float(ev.start_ns + ev.duration_ns),
                                    module_name(ev.name)))
    ops.sort(key=lambda o: o[1])
    modules.sort()
    for op, mod in zip(ops, _modules_of(ops, modules)):
        op[3] = mod
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                if ev.name == "hq.window":
                    window = [float(ev.start_ns),
                              float(ev.start_ns + ev.duration_ns)]
                else:
                    spans.append([ev.name, float(ev.start_ns),
                                  float(ev.duration_ns)])
    return {"device_ops": ops, "host_spans": spans, "window": window}


def layout(path: str, n_names: int = 12) -> dict:
    """Planes, their lines, event counts and the commonest event names:
    what to look at by hand before trusting ``extract``."""
    from collections import Counter

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            names = Counter(ev.name for ev in line.events)
            lines[line.name] = {"events": sum(names.values()),
                                "names": names.most_common(n_names)}
        out[plane.name] = lines
    return out


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def clip(start: float, dur: float, lo: float, hi: float) -> tuple:
    s, e = max(start, lo), min(start + dur, hi)
    return (s, e) if e > s else None


def busy_intervals(rec: dict) -> list:
    """Union of device-op intervals inside the window, merged and sorted."""
    lo, hi = rec["window"]
    iv = sorted(filter(None, (clip(s, d, lo, hi)
                              for _, s, d, _ in rec["device_ops"])))
    merged = []
    for s, e in iv:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_seconds(rec: dict) -> float:
    return sum(e - s for s, e in busy_intervals(rec)) * 1e-9


def window_seconds(rec: dict) -> float:
    lo, hi = rec["window"]
    return (hi - lo) * 1e-9


def idle_gaps(rec: dict) -> list:
    """[start, end] of the device's idle stretches inside the window."""
    lo, hi = rec["window"]
    gaps, t = [], lo
    for s, e in busy_intervals(rec):
        if s > t:
            gaps.append([t, s])
        t = max(t, e)
    if hi > t:
        gaps.append([t, hi])
    return gaps


def innermost_span(rec: dict, t: float) -> str:
    """The shortest benchmark span on the host that holds instant ``t``."""
    best = None
    for name, s, d in rec["host_spans"]:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "no span"


def idle_by_span(rec: dict, top: int = 10) -> list:
    """Idle device seconds by what the host was doing (the innermost
    benchmark span at each gap's middle), largest first."""
    out: dict = {}
    for s, e in idle_gaps(rec):
        name = innermost_span(rec, 0.5 * (s + e))
        out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return sorted(([k, v] for k, v in out.items()),
                  key=lambda kv: -kv[1])[:top]


def op_seconds(rec: dict, match=None) -> dict:
    """Device seconds inside the window by op name (``match(name, module)``
    filters)."""
    lo, hi = rec["window"]
    out: dict = {}
    for name, s, d, module in rec["device_ops"]:
        if match is not None and not match(name, module):
            continue
        c = clip(s, d, lo, hi)
        if c:
            out[name] = out.get(name, 0.0) + (c[1] - c[0]) * 1e-9
    return out


def top_ops(rec: dict, top: int = 10) -> list:
    return sorted(([k, v] for k, v in op_seconds(rec).items()),
                  key=lambda kv: -kv[1])[:top]


def save(rec: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(rec, f)


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def is_gather_kernel(name: str, module: str) -> bool:
    """Trace events of the gather+score Pallas kernel: its custom call is
    the HLO instruction ``%gather_score_blocks.<n>`` in whatever program
    launched it."""
    return name.lstrip("%").startswith("gather_score_blocks")
