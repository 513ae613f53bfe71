"""Reduce a profiler trace of the traced sub-window to numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``. Device planes are ``/device:TPU:<n>``;
their leaf operations are the events of the ``XLA Ops`` line. The
benchmark's own host spans (``bench.*`` TraceAnnotations) lie on a host
plane on the same clock.

- busy: the union of the leaf-op intervals inside the window, averaged
  over the chips used;
- window: from the first ``bench.*`` span's start to the last one's end;
- top device ops by time, and the longest idle gaps, each named by the
  ``bench.*`` span that covers most of it.

The leaf ops carry no op path (no ``tf_op`` or name scope) in this
JAX's TPU trace, only the HLO instruction, so time is not grouped by
model layer here.
"""

import glob
import os

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def load(trace_dir):
    """(device ops {plane: [(start, end, name)]}, host spans
    [(start, end, name)]) from the newest xplane under ``trace_dir``;
    times in ns."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return {}, []
    pd = ProfileData.from_file(paths[-1])
    ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                short_name(ev.name)))
            ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    return ops, spans


def short_name(hlo):
    """``%fusion.27 = (...) fusion(...), kind=kLoop, ...`` -> ``fusion.27
    kLoop``: the instruction's name and, where given, its fusion kind or
    custom-call target."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    for key in (", kind=", "custom_call_target=\""):
        if key in hlo:
            name += " " + hlo.split(key, 1)[1].split(",")[0].strip('"')[:40]
    return name


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(ops, spans, n_chips=1):
    """Numbers of the traced window, or None when no device op ran in it.
    ``ops`` and ``spans`` as ``load`` returns them."""
    if not spans or not any(ops.values()):
        return None
    w0 = min(s for s, _, _ in spans)
    w1 = max(e for _, e, _ in spans)
    planes = sorted(ops)[:n_chips]
    busy_ns, by_name, gaps = 0.0, {}, []
    for p in planes:
        inside = [(max(s, w0), min(e, w1), n)
                  for s, e, n in ops[p] if e > w0 and s < w1]
        merged = _union([(s, e) for s, e, _ in inside])
        busy_ns += sum(e - s for s, e in merged)
        for s, e, n in inside:
            by_name[n] = by_name.get(n, 0.0) + (e - s)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    if busy_ns <= 0:
        return None
    window_ns = float(w1 - w0)
    busy_s = busy_ns / len(planes) / 1e9
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        cover = {}
        for hs, he, hn in spans:
            o = min(e, he) - max(s, hs)
            if o > 0:
                cover[hn] = cover.get(hn, 0) + o
        name = max(cover, key=cover.get) if cover else "no bench span"
        named.append([name, (e - s) / 1e9])
    return {
        "busy_s": busy_s,
        "window_s": window_ns / 1e9,
        "device_ops": [[n, t / len(planes) / 1e9] for n, t in top_ops],
        "idle_gaps": named,
    }
