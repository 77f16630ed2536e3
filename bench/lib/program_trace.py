"""The program's own spans, counters and device scopes in a profiler
trace, on top of the reduction in `trace.py`.

`trace.py` keeps the benchmark's host spans (`bench.*`) and the device
ops.  The program names its own parts too: host spans
`repro.*` (`Batcher.join`/`step`, `RoundEngine.run_round` and their
parts, with `tenant`/`slot`/`round` arguments) and, on the device, a
`jax.named_scope` per IR step of a training turn plus `optimizer`.
`load` reads those spans beside `trace.load`'s, with every program
launch and every module execution, on the same host clock.

The op events carry no HLO metadata, so the IR step of a device op is
read from the compiled program's text (`Compiled.as_text()`,
`round_text`): each instruction's `op_name` holds the scope path the
program traced it under, and `ir_step` maps that path to one step.

The readers take the context `trace.read_window` builds, with
`"trace"` a `ProgramTrace` and, where device time by IR step is wanted,
`"hlo_text"` the round's compiled text; each returns None where the
trace holds nothing for it.  Nothing in the benchmark calls them yet:
`trace.read_window` must load the trace through `load`, and the drivers
must pass the text and the counters' window deltas (PERF.md, section 7).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import statistics

from bench.lib import trace as tr
from bench.lib.trace import CONTAINERS, clip, union

PROGRAM_PREFIX = "repro."
# the program's device scopes (`repro.engine.program`): the IR steps of
# a turn by class name, then the optimizer step
STEP_SCOPES = ("ClientFwd", "SendCut", "ServerFwdBwd", "RecvGrad",
               "ClientBwd", "WeightHandoff", "optimizer")
UNMAPPED = "unmapped"


@dataclasses.dataclass
class ProgramTrace(tr.Trace):
    """`trace.Trace` with the program's spans among `spans`, and the
    launches and module executions."""
    # host start of every program launch, sorted
    launches: list = dataclasses.field(default_factory=list)
    # device plane name -> [(module name, start_ns, end_ns)] executions
    modules: dict = dataclasses.field(default_factory=dict)


def load(path) -> ProgramTrace:
    """`trace.load`, plus the `repro.*` host spans, the program launches
    and the module executions, on the host clock by the same shift."""
    from jax.profiler import ProfileData
    t = tr.load(path)
    spans, launches, modules = list(t.spans), [], {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name in t.shift_ns:
            d = t.shift_ns[plane.name]
            modules[plane.name] = sorted(
                ((module_name(e.name), int(e.start_ns) + d,
                  int(e.end_ns) + d)
                 for line in plane.lines if line.name == tr.MODULES_LINE
                 for e in line.events), key=lambda m: m[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.end_ns)))
                    elif e.name == tr.LAUNCH:
                        launches.append(int(e.start_ns))
    return ProgramTrace(ops=t.ops, spans=sorted(spans, key=lambda s: s[1]),
                        shift_ns=t.shift_ns, launches=sorted(launches),
                        modules=modules)


def module_name(text: str) -> str:
    """A program execution's module name, less the fingerprint the
    trace appends: `jit__round(8415...)` -> `jit__round`, the name in
    the compiled text's `HloModule` line."""
    return re.sub(r"\(\d+\)$", "", text)


def round_text(sess, batch) -> str:
    """The compiled text of a training session's round program, for the
    op -> IR step map; fetch it after the window, in a traced run only.
    It is compiled afresh, outside the persistent cache: that cache's
    key leaves metadata out, so an entry written by a program without
    the scopes would come back without them.  The wrapper keeps the
    module's name (`jit__round`) and its donation, so the instruction
    names are those of the program the trace ran."""
    import jax
    engine = sess.engine

    def _round(state, batches):
        return engine._round(state, batches)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jax.jit(_round, donate_argnums=(0,)).lower(
            sess.state, sess._prep(batch)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


# ---------------------------------------------------------------------------
# the program's host spans
# ---------------------------------------------------------------------------

def busy_index(ops) -> tuple:
    """The union of op intervals as (starts, ends, running lengths), for
    `busy_between` in log time."""
    iv = union([(s, e) for _, s, e in ops])
    cum = [0]
    for s, e in iv:
        cum.append(cum[-1] + e - s)
    return [s for s, _ in iv], [e for _, e in iv], cum


def busy_between(index, lo, hi) -> int:
    """ns of [lo, hi] in which some op ran (`busy_index`)."""
    starts, ends, cum = index
    i = bisect.bisect_right(ends, lo)          # first interval ending > lo
    j = bisect.bisect_left(starts, hi)         # intervals starting < hi
    if j <= i:
        return 0
    return (cum[j] - cum[i] - max(0, lo - starts[i])
            - max(0, ends[j - 1] - hi))


def idle_ns(index, intervals) -> int:
    """Device idle time inside the union of host `intervals`."""
    return sum(e - s - busy_between(index, s, e)
               for s, e in union(intervals))


def spans_named(spans, name: str, lo, hi) -> list:
    """[(start, end)] of the host spans called `name` wholly in
    [lo, hi]."""
    return [(s, e) for n, s, e in spans if n == name and lo <= s and e <= hi]


def launches_in(launches, lo, hi) -> int:
    """Program launches the host began in [lo, hi)."""
    return bisect.bisect_left(launches, hi) - bisect.bisect_left(launches, lo)


def self_ns(spans, span) -> int:
    """A span's self time: its length less the union of the other spans
    that lie within it (its children and theirs)."""
    _, lo, hi = span
    inner = [(s, e) for n, s, e in spans
             if lo <= s and e <= hi and (n, s, e) != span]
    return hi - lo - sum(e - s for s, e in union(inner))


def innermost(spans, lo, hi) -> list:
    """[(start, end, name)] tiling [lo, hi]: at each instant the
    innermost (latest-starting) host span, as `trace.span_at` names it, or
    'outside-spans'."""
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    order = sorted(spans, key=lambda sp: sp[1])
    active, k, out = [], 0, []
    for a, b in zip(cuts, cuts[1:]):
        while k < len(order) and order[k][1] <= a:
            active.append(order[k])
            k += 1
        active = [sp for sp in active if sp[2] > a]
        name = (max(active, key=lambda sp: sp[1])[0] if active
                else "outside-spans")
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def idle_by_span(index, spans, lo, hi) -> collections.Counter:
    """Device idle ns in [lo, hi] by the innermost host span the host was
    in at the time (every gap, split where the host moved on)."""
    c = collections.Counter()
    for a, b, name in innermost(spans, lo, hi):
        idle = b - a - busy_between(index, a, b)
        if idle:
            c[name] += idle
    return c


def program_spans(spans, lo, hi) -> list:
    """[(start, end)] of the program's own host spans, clipped to
    [lo, hi]."""
    return clip([(s, e) for n, s, e in spans
                  if n.startswith(PROGRAM_PREFIX)], lo, hi)


def idle_in_program(ctx):
    """% of the traced window in which the device sat idle while the host
    was inside a program span, averaged over the chips; None where the
    program records no spans."""
    t, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
    inside = program_spans(t.spans, lo, hi)
    if not inside:
        return None
    idle = statistics.fmean(idle_ns(busy_index(ops), inside)
                            for ops in ctx["planes"])
    return 100.0 * idle / (hi - lo)


def span_idle_ms(ctx, name: str):
    """Median over the spans `name` wholly in the traced window of the
    device's idle time inside each (first chip), in ms; None where there
    are none."""
    t = ctx["trace"]
    found = spans_named(t.spans, name, ctx["lo"], ctx["hi"])
    if not found:
        return None
    index = busy_index(ctx["planes"][0])
    return 1e-6 * statistics.median(idle_ns(index, [sp]) for sp in found)


def span_launches(ctx, name: str):
    """Median over the spans `name` wholly in the traced window of the
    program launches inside each; None where there are none."""
    t = ctx["trace"]
    found = spans_named(t.spans, name, ctx["lo"], ctx["hi"])
    if not found:
        return None
    return float(statistics.median(launches_in(t.launches, s, e)
                                   for s, e in found))


# ---------------------------------------------------------------------------
# device time by IR step
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"\s*(?:ROOT\s+)?(%[^\s=]+) = ")
_COMPUTATION = re.compile(r"(?:ENTRY\s+)?(%\S+) .*\{\s*$")
_CALLS = re.compile(r"calls=(%[^\s,}]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WORD = re.compile(r"[A-Za-z_]\w*")


def hlo_op_names(text: str) -> dict:
    """{module name: {instruction name: op_name}} from compiled HLO text
    (`Compiled.as_text()`, one or more modules); instructions without an
    `op_name` are left out."""
    out, cur = {}, None
    for line in text.splitlines():
        if line.startswith("HloModule "):
            cur = out.setdefault(line.split()[1].rstrip(","), {})
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            n = _OP_NAME.search(line)
            if n:
                cur[m.group(1)] = n.group(1)
    return out


def hlo_held_steps(text: str) -> dict:
    """{module name: {instruction name: IR steps}} for each instruction
    that calls a computation (a fusion): the steps its own `op_name`
    and the instructions inside the called computation name.  A fusion
    is one op with its root's `op_name`, but XLA may fuse one step's
    work into another's op (the optimizer's update into the weight
    gradient's output fusion)."""
    inside, calls, mod, comp = {}, {}, None, None
    for line in text.splitlines():
        if line.startswith("HloModule "):
            mod = line.split()[1].rstrip(",")
            inside[mod], calls[mod] = collections.defaultdict(set), {}
            continue
        m = _COMPUTATION.match(line)
        if m and not line[0].isspace():
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if m is None or mod is None:
            continue
        n = _OP_NAME.search(line)
        step = ir_step(n.group(1)) if n else None
        if step:
            inside[mod][comp].add(step)
        c = _CALLS.search(line)
        if c:
            calls[mod][m.group(1)] = (step, c.group(1))
    return {mod: {instr: ({own} - {None}) | inside[mod][callee]
                  for instr, (own, callee) in calls[mod].items()}
            for mod in calls}


def ir_step(path: str):
    """The IR step of an op by its `op_name` path, or None.  The last
    path component that names a scope of STEP_SCOPES wins; `ClientFwd`
    inside a `transpose(...)` is the client's backward, so reads as
    `ClientBwd` (`transpose(jvp(ClientFwd))` -> `ClientBwd`; an op
    traced in `ClientBwd/transpose(jvp())` is `ClientBwd` already)."""
    step = None
    for part in path.split("/"):
        words = _WORD.findall(part)
        named = [w for w in words if w in STEP_SCOPES]
        if named:
            step = named[-1]
            if step == "ClientFwd" and "transpose" in words:
                step = "ClientBwd"
    return step


def _ops_by_module(ops, modules, lo, hi):
    """Each op in [lo, hi], loops and other containers left out, as
    (module name or None, instruction name, ns in the window).  An op
    belongs to the module execution it starts in."""
    starts = [s for _, s, _ in modules]
    for name, s, e in ops:
        if name.startswith(CONTAINERS):
            continue
        s2, e2 = max(s, lo), min(e, hi)
        if e2 <= s2:
            continue
        i = bisect.bisect_right(starts, s) - 1
        yield (modules[i][0] if i >= 0 else None,
               name.partition(" = ")[0], e2 - s2)


def mapped_ops(ops, modules, names: dict, lo, hi):
    """Each op in [lo, hi] (`_ops_by_module`) as (instruction name, IR
    step or UNMAPPED, ns in the window); UNMAPPED where the op's module
    has no text or its `op_name` names no step.  `names` is
    `hlo_op_names`."""
    for mod, instr, ns in _ops_by_module(ops, modules, lo, hi):
        path = names.get(mod, {}).get(instr)
        yield instr, (ir_step(path) if path else None) or UNMAPPED, ns


def _planes(ctx) -> list:
    """(module executions, ops) of each traced chip."""
    t = ctx["trace"]
    name = {id(ops): plane for plane, ops in t.ops.items()}
    modules = getattr(t, "modules", {})
    return [(modules.get(name.get(id(ops)), []), ops)
            for ops in ctx["planes"]]


def _by_op(ctx):
    names = hlo_op_names(ctx.get("hlo_text") or "")
    for mods, ops in _planes(ctx):
        yield from mapped_ops(ops, mods, names, ctx["lo"], ctx["hi"])


def held_times(ctx) -> collections.Counter:
    """Device ns of the ops that hold each IR step's work, in their own
    `op_name` or inside the computation they call (`hlo_held_steps`);
    an op counts under every step it holds."""
    text = ctx.get("hlo_text") or ""
    names, held = hlo_op_names(text), hlo_held_steps(text)
    c = collections.Counter()
    for mods, ops in _planes(ctx):
        for mod, instr, ns in _ops_by_module(ops, mods, ctx["lo"],
                                             ctx["hi"]):
            path = names.get(mod, {}).get(instr)
            steps = set(held.get(mod, {}).get(instr, ()))
            steps |= {ir_step(path)} if path else set()
            for step in steps - {None}:
                c[step] += ns
    return c


def step_times(ctx):
    """Device ns by IR step over the traced chips (cached in `ctx`);
    None without the compiled text (`ctx["hlo_text"]`) or where no op
    maps to a step (a program without device scopes)."""
    if "step_ns" not in ctx:
        c = collections.Counter()
        for _, step, ns in _by_op(ctx):
            c[step] += ns
        ctx["step_ns"] = c if set(c) - {UNMAPPED} else None
    return ctx["step_ns"]


def step_share(ctx, *steps):
    """% of the chips' busy time spent in the given IR steps; None where
    `step_times` finds nothing."""
    c = step_times(ctx)
    if c is None or not ctx.get("busy_s"):
        return None
    return 100.0 * sum(c[k] for k in steps) * 1e-9 / (
        ctx["busy_s"] * len(ctx["planes"]))


def unmapped_ops(ctx, top: int = 8) -> list:
    """The costliest ops of the window that map to no IR step:
    [[instruction name, seconds over the chips], ...]."""
    c = collections.Counter()
    for instr, step, ns in _by_op(ctx):
        if step == UNMAPPED:
            c[instr] += ns
    return [[k, v * 1e-9] for k, v in c.most_common(top)]


def program_summary(ctx, top: int = 12) -> dict:
    """What the program's spans and scopes say of the traced window, for
    a diagnostic line: device idle seconds by the innermost host span
    the host was in; device seconds by IR step, by the steps each op
    holds, and of the costliest unmapped ops."""
    t, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
    idle = idle_by_span(busy_index(ctx["planes"][0]), t.spans, lo, hi)
    out = {"idle_s_by_span": [[k, v * 1e-9]
                              for k, v in idle.most_common(top)]}
    steps = step_times(ctx)
    if steps is not None:
        out["device_s_by_step"] = {k: v * 1e-9 for k, v in steps.items()}
        out["device_s_holding_step"] = {
            k: v * 1e-9 for k, v in held_times(ctx).items()}
        out["unmapped_ops"] = unmapped_ops(ctx)
    return out
