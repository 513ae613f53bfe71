"""The program's own spans and the device ops' model scopes in a traced run.

``trace.py`` times the device over the traced sub-window and names its
idle gaps by the benchmark's ``bench.*`` spans. This module reads more
from the same profile, and from the program's span file:

- the program's spans on the host planes: ``loader.*`` and
  ``train_step``, which the program records as profiler annotations
  while its telemetry is armed (the traced run arms it);
- each device op's model scope: the op path in the op event's own stats
  where the trace has one, else a join of the op's HLO instruction name
  (``%fusion.203 = ...``) against the HLO modules the profile carries in
  its ``/host:metadata`` plane. An instruction's scope is its op name
  without the last element; a fusion's is the scope most of the op names
  in its called computations share;
- from the program's JSONL span file (its telemetry directory), the
  window's ``train_step`` spans: their mean duration, and the program span
  that covers most of each of the longest intervals between them.

An op counts to a layer when an element of its scope equals the layer's
name (``attention``, ``optimizer``). The per-layer readers under
``metrics/`` call ``reading`` with the harness's record; a program that
records no such span or scope reads nothing.
"""

import bisect
import collections
import glob
import json
import os
import traceback

from chipbench import trace

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
BENCH_PREFIX = "bench."
PROGRAM_PREFIXES = ("loader.", "train_step")
STEP_SPAN = "train_step"
EPOCH_SPAN = "loader.epoch"  # spans the whole epoch: covers every interval
LAYERS = ("attention", "optimizer")
N_TOP_OPS = 10
N_GAPS = 10
N_INTERVALS = 5


def instruction(hlo):
    """``%fusion.27 = bf16[..] fusion(..), ...`` -> ``fusion.27``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def scope_of(op_name):
    """The scope of an op name: its path without the last element (the
    primitive). "" for a name with no path."""
    return op_name.rpartition("/")[0]


def resolved(scope):
    """True when the scope names a part of the program below its outermost
    ``jit(...)``."""
    return len(scope.split("/")) >= 2


def layer_of(scope):
    elements = scope.split("/")
    for layer in LAYERS:
        if layer in elements:
            return layer
    return None


def module_scopes(rows):
    """{instruction: scope} of one HLO module, from the rows
    ``xplane.hlo_instructions`` gives: an instruction that calls
    computations (a fusion, a call, a loop) takes the scope most of the op
    names in them share, counted through nested calls; any other its own
    op name's scope. Instructions with neither are left out."""
    by_comp = collections.defaultdict(list)
    for comp, _, _, op_name, called in rows:
        by_comp[comp].append((op_name, called))
    votes = {}

    def count(comp, seen):
        if comp in votes:
            return votes[comp]
        c = collections.Counter()
        for op_name, called in by_comp.get(comp, ()):
            if scope_of(op_name):
                c[scope_of(op_name)] += 1
            for sub in called:
                if sub not in seen:
                    c.update(count(sub, seen | {sub}))
        votes[comp] = c
        return c

    out = {}
    for _, name, _, op_name, called in rows:
        c = collections.Counter()
        for sub in called:
            c.update(count(sub, {sub}))
        if c:
            out[name] = c.most_common(1)[0][0]
        elif scope_of(op_name):
            out[name] = scope_of(op_name)
    return out


def _newest_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(trace_dir):
    """(ops, spans) of the newest profile under ``trace_dir``, times in ns:
    ops ``{device plane: [(start, end, instruction, scope or None)]}`` of
    the ``XLA Ops`` line; spans ``[(start, end, name, {arg: value})]`` of
    the host planes' ``bench.*``, ``loader.*`` and ``train_step`` events.
    ({}, []) when there is no profile."""
    from jax.profiler import ProfileData
    from chipbench import xplane

    path = _newest_xplane(trace_dir)
    if path is None:
        return {}, []
    with open(path, "rb") as f:
        raw = f.read()
    scopes = {}
    for name, plane in xplane.planes(raw):
        if name != METADATA_PLANE:
            continue
        for module, stat, value in xplane.metadata_stats(plane):
            if stat == HLO_STAT:
                scopes[module] = module_scopes(
                    xplane.hlo_instructions(value)[1])
    pd = ProfileData.from_serialized_xspace(raw)
    ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            modules, evs = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules = sorted((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name)
                                     for ev in line.events)
                elif line.name == OPS_LINE:
                    evs = list(line.events)
            starts = [m[0] for m in modules]
            out = []
            for ev in evs:
                start, end = ev.start_ns, ev.start_ns + ev.duration_ns
                instr = instruction(ev.name)
                own = dict(ev.stats).get("tf_op")
                if own:
                    scope = scope_of(str(own))
                else:
                    i = bisect.bisect_right(starts, start) - 1
                    module = modules[i][2] if i >= 0 and \
                        start < modules[i][1] else None
                    scope = scopes.get(module, {}).get(instr)
                out.append((start, end, instr, scope))
            ops[plane.name] = out
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(BENCH_PREFIX) or \
                            ev.name.startswith(PROGRAM_PREFIXES):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name,
                                      dict(ev.stats)))
    return ops, spans


def covering(s, e, spans):
    """(name, share of [s, e] it covers) of the deepest span covering most
    of the interval: of the spans that cover at least half of it, the
    shortest. (None, the largest share any span covers) when none covers
    half. ``spans`` are (start, end, name, ...) tuples."""
    if e <= s:
        return None, 0.0
    best, most = None, 0
    for span in spans:
        ps, pe, name = span[0], span[1], span[2]
        o = min(e, pe) - max(s, ps)
        most = max(most, o)
        if 2 * o >= e - s and (best is None or pe - ps < best[0]):
            best = (pe - ps, name, o)
    if best is None:
        return None, most / (e - s)
    return best[1], best[2] / (e - s)


def reduce(ops, spans, n_chips=1):
    """Scopes and program spans of the traced sub-window, or None when no
    device op ran in it. The window is the ``bench.*`` spans' extent, as
    ``trace.py`` has it.

    - ``resolved_share``: share of the ops' device time with a scope;
    - ``layer_share``: {layer: share of the device's busy time in ops of
      that layer's scope}, mean over the chips; a layer no op belongs to
      is left out;
    - ``top_ops``: [instruction, seconds, scope] by device time;
    - ``idle_gaps``: [seconds, deepest program span covering most of the
      gap, the bench span that covers most of it], longest first."""
    bench = [sp for sp in spans if sp[2].startswith(BENCH_PREFIX)]
    program = [sp for sp in spans if not sp[2].startswith(BENCH_PREFIX)]
    if not bench or not any(ops.values()):
        return None
    w0 = min(sp[0] for sp in bench)
    w1 = max(sp[1] for sp in bench)
    planes = sorted(ops)[:n_chips]
    shares = collections.defaultdict(list)
    timed = scoped = 0.0
    by_op, gaps = {}, []
    for p in planes:
        inside = [(max(s, w0), min(e, w1), n, sc)
                  for s, e, n, sc in ops[p] if e > w0 and s < w1]
        merged = trace._union([(s, e) for s, e, _, _ in inside])
        busy = sum(e - s for s, e in merged)
        if busy <= 0:
            continue
        per_layer = collections.Counter()
        for s, e, n, sc in inside:
            timed += e - s
            if sc and resolved(sc):
                scoped += e - s
                layer = layer_of(sc)
                if layer:
                    per_layer[layer] += e - s
            t, _ = by_op.get(n, (0.0, sc))
            by_op[n] = (t + e - s, sc)
        for layer in LAYERS:
            shares[layer].append(per_layer[layer] / busy)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        gaps += [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    if timed <= 0:
        return None
    layer_share = {layer: sum(v) / len(v) for layer, v in shares.items()
                   if any(v)}
    top = sorted(by_op.items(), key=lambda kv: -kv[1][0])[:N_TOP_OPS]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:N_GAPS]:
        named.append([(e - s) / 1e9, covering(s, e, program)[0],
                      covering(s, e, bench)[0]])
    return {
        "resolved_share": scoped / timed,
        "layer_share": layer_share,
        "top_ops": [[n, t / len(planes) / 1e9, sc]
                    for n, (t, sc) in top],
        "idle_gaps": named,
    }


def traced_steps(spans):
    """(lowest ``step_num`` of the profile's ``train_step`` spans or None,
    number of ``bench.dispatch`` spans, how many of them hold exactly one
    ``train_step`` span)."""
    steps = [sp for sp in spans if sp[2] == STEP_SPAN]
    dispatch = [sp for sp in spans if sp[2] == "bench.dispatch"]
    one = sum(1 for ds, de, _, _ in dispatch
              if sum(1 for s, e, _, _ in steps if ds <= s and e <= de) == 1)
    nums = [int(sp[3]["step_num"]) for sp in steps if "step_num" in sp[3]]
    return (min(nums) if nums else None), len(dispatch), one


def read_jsonl(metrics_dir):
    """The complete ("X") and instant ("i") records of every span file
    the program wrote under ``metrics_dir``."""
    out = []
    for path in sorted(glob.glob(os.path.join(metrics_dir, "trace-*.jsonl"))):
        with open(path, encoding="utf-8") as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("ph") in ("X", "i"):
                    out.append(rec)
    return out


def window_steps(records, first_traced, seconds):
    """The window's ``train_step`` records, by step: those before the
    first traced step whose start lies within ``seconds`` of the last of
    them (the step the window left in flight). That leaves out set-up's
    steps but the one it left in flight for the window to open on."""
    steps = sorted((r for r in records if r["name"] == STEP_SPAN
                    and "step" in r.get("args", {})
                    and r["args"]["step"] < first_traced),
                   key=lambda r: r["args"]["step"])
    if not steps:
        return []
    t_last = steps[-1]["ts"]
    return [r for r in steps if r["ts"] >= t_last - seconds * 1e6]


def longest_intervals(records, steps, n=N_INTERVALS):
    """[[interval ms, deepest program span covering most of it, share
    covered, instants inside it]] of the ``n`` longest intervals between
    consecutive window steps' starts."""
    spans = [(r["ts"], r["ts"] + r["dur"], r["name"]) for r in records
             if r["ph"] == "X" and r["name"].startswith(PROGRAM_PREFIXES)
             and r["name"] not in (STEP_SPAN, EPOCH_SPAN)]
    points = [(r["ts"], r["name"]) for r in records if r["ph"] == "i"]
    starts = [r["ts"] for r in steps]
    pairs = sorted(zip(starts, starts[1:]), key=lambda p: p[0] - p[1])
    out = []
    for s, e in pairs[:n]:
        name, share = covering(s, e, spans)
        out.append([(e - s) / 1e3, name, share,
                    sorted({pn for pt, pn in points if s <= pt < e})])
    return out


def _trace_dirs():
    """(telemetry directory, profile directory) of the traced run: the
    harness arms the program's telemetry in ``<work>/metrics`` and traces
    into ``<work>/trace``."""
    from lddl_tpu import observability as obs
    metrics = obs.metrics_dir()
    if not metrics:
        return None, None
    return metrics, os.path.join(os.path.dirname(metrics), "trace")


def reading(record):
    """This run's readings, computed once and kept in ``record`` for the
    other readers, and logged as a ``trace_detail`` line: the reduction's
    keys (``layer_share`` empty when no device op ran), ``dispatch_ms``
    (None without ``train_step`` spans) and the window's longest step
    intervals. None when the run left no profile."""
    if "program_trace" in record:
        return record["program_trace"]
    record["program_trace"] = None
    try:
        r = _read(record)
    except Exception:  # noqa: BLE001 - a reader must not fail the run
        traceback.print_exc()
        return None
    if r is not None:
        print("trace_detail: {}".format(json.dumps(r, sort_keys=True)),
              flush=True)
    record["program_trace"] = r
    return r


def _read(record):
    metrics, trace_dir = _trace_dirs()
    if trace_dir is None or not os.path.isdir(trace_dir):
        return None
    ops, spans = load(trace_dir)
    r = reduce(ops, spans, n_chips=record.get("chips", 1)) or {
        "layer_share": {}}
    first, r["dispatches"], r["dispatches_with_one_step"] = \
        traced_steps(spans)
    r["dispatch_ms"] = None
    r["longest_step_intervals"] = []
    if first is not None:
        from lddl_tpu import observability as obs
        obs.flush()
        records = read_jsonl(metrics)
        steps = window_steps(records, first, record["seconds"])
        if steps:
            r["dispatch_ms"] = sum(x["dur"] for x in steps) / len(steps) / 1e3
            r["window_steps"] = len(steps)
            r["longest_step_intervals"] = longest_intervals(records, steps)
    return r
