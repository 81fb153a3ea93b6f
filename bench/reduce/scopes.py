"""Device time by SCOPE: which part of a step program each operation of a
profile belongs to, and what XLA's `fusion`, `copy` and `while` around the
kernels are made of.

`xplane.Reduced` reduces a trace by an operation's NAME alone, so everything
that is no Mosaic kernel is a row called `fusion` or `copy`. The program puts
every operation of its step programs under one of twenty named scopes
(`bigdl_tpu/obs/scopes.py`); the compiler keeps the JAX name stack of an
operation as its metadata, and the profile carries it as the statistic
`tf_op` of the event's METADATA:

    jit(engine_decode)/attn.proj/jit(_qmm)/qmatmul/pallas_call:

An operation's SCOPE is the innermost component of that path that is a name
of the vocabulary; one with none is `unscoped`. A `fusion` carries the
metadata of its ROOT instruction, so an operation XLA fused across two
scopes is all given to the scope its root came from. A `while` (a `scan` of
layers) that stands under no scope is named `while`: the loop itself, and only
its SELF time (what its body's operations do not cover: `self_times`). So is
an operation whose name stack ENDS in `while`: the compiler made it for the
loop (a copy that re-lays a carried buffer, work it moved out of the body)
and gave it the loop's metadata.

Where the metadata is read from: `jax.profiler.ProfileData` hands out an
event's own statistics (`device_offset_ps`, `device_duration_ps`) and not its
metadata's; the one `xplane_pb2` of this installation is TensorFlow's, which
took 25 s to import beside a TPU runtime (chip run, PR 52). So this file reads
the protobuf wire format itself, and only what it needs of it: of each device
plane the two maps `event_metadata` and `stat_metadata`, the lines skipped
whole (they are what `xplane.load` has read already). An event is tied to its
metadata by the program it ran in (the `XLA Modules` event it starts in names
the program's id in its parentheses, the metadata carries `program_id`) and
its own name (`fusion.16`, unique in a program, the metadata's
`display_name`).

A failure of any kind (no trace, no `tf_op`, other bytes than expected)
gives None from every reader and ONE printed line saying why: `account`
never raises. The names an OLDER tree used are in `GROUPS` too
(`norm_rope`), so its trace reads numbers, with a large `unscoped`.

All times are seconds on the trace's clock, as in `xplane`.
"""

from __future__ import annotations

import bisect
import dataclasses
import os
import time

from bench.reduce import xplane

#: the six groups the metrics report, and the scopes of each. The names are
#: `bigdl_tpu.obs.scopes.VOCABULARY`'s (tests/bench holds the two together)
#: and what the tree before PR 52 called its spans.
GROUPS = {
    "mixer": ("attn", "attn.proj", "attn.rope", "attn.gate", "mamba2",
              "power_retention_prefill", "mamba2_prefill"),
    "ffn": ("ffn", "ffn.dense", "moe.router", "moe.shared", "moe.dispatch",
            "moe.experts", "moe.combine"),
    "norm": ("norm", "norm_rope"),
    "head": ("lm_head", "sample", "block.reveal", "block.store"),
    "engine": ("engine", "while"),
    "unscoped": ("unscoped",),
}
GROUP_OF = {scope: g for g, scopes in GROUPS.items() for scope in scopes}
LOOP, UNSCOPED = "while", "unscoped"  # rows of the table, names of no scope
_NAMED = frozenset(GROUP_OF) - {LOOP, UNSCOPED}

#: the stable names of the Mosaic kernels (`bench/metrics/kernel.*` read the
#: same names); any other operation is XLA's
KERNELS = frozenset((
    "qmatmul", "qmatmul_lora", "moe_qmatmul", "paged_decode_attention",
    "paged_latent_decode_attention", "flash_attention",
    "power_retention_decode", "mamba2_decode"))

PROGRAMS = ("engine_decode", "engine_paged_prefill", "generate_tokens")
KEY = "scopes.account"  # where `account` keeps what it built, in `Run.extra`


# ---- the wire format -------------------------------------------------------
#
# XSpace { repeated XPlane planes = 1 }
# XPlane { string name = 2; repeated XLine lines = 3;
#          map<int64, XEventMetadata> event_metadata = 4;
#          map<int64, XStatMetadata> stat_metadata = 5 }
# a map's entry { int64 key = 1; value = 2 }
# XEventMetadata { string name = 2; string display_name = 4;
#                  repeated XStat stats = 5 }
# XStatMetadata { int64 id = 1; string name = 2 }
# XStat { int64 metadata_id = 1; uint64 uint64_value = 3;
#         int64 int64_value = 4; string str_value = 5; uint64 ref_value = 7 }

def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, wire type, value) of a message's top-level fields:
    an integer for a varint, (start, end) for a length-delimited one."""
    while i < end:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire == 1:
            val, i = None, i + 8
        elif wire == 5:
            val, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield tag >> 3, wire, val
    if i != end:
        raise ValueError(f"a message ends at byte {i}, not {end}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _entry_value(buf, span):
    """The value (field 2) of a map's entry."""
    for num, wire, val in _fields(buf, *span):
        if num == 2 and wire == 2:
            return val
    return None


def read_metadata(path: str) -> dict:
    """device plane name -> {(program id, the operation's own name): (its
    `tf_op`, "" where the compiler gave it none; the whole HLO
    instruction)} of the profile at `path`."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, wire, plane in _fields(buf, 0, len(buf)):
        if num != 1 or wire != 2:
            continue
        name, events, stats = "", [], {}
        for num, wire, val in _fields(buf, *plane):
            if wire != 2:
                continue
            if num == 2:
                name = _text(buf, val)
            elif num == 4:
                events.append(_entry_value(buf, val))
            elif num == 5:
                sid, sname = None, ""
                for n2, w2, v2 in _fields(buf, *_entry_value(buf, val)):
                    if n2 == 1:
                        sid = v2
                    elif n2 == 2:
                        sname = _text(buf, v2)
                stats[sid] = sname
        if not name.startswith(xplane.DEVICE_PREFIX):
            continue
        ids = {s: i for i, s in stats.items()}
        tf_op, program_id = ids.get("tf_op"), ids.get("program_id")
        table = out[name] = {}
        for ev in events:
            if ev is None:
                continue
            own, hlo, op, prog = "", "", "", None
            for num, wire, val in _fields(buf, *ev):
                if num == 2:
                    hlo = _text(buf, val)
                elif num == 4:
                    own = _text(buf, val)
                elif num == 5:
                    sid = text = ref = number = None
                    for n2, w2, v2 in _fields(buf, *val):
                        if n2 == 1:
                            sid = v2
                        elif n2 == 5:
                            text = v2
                        elif n2 == 7:
                            ref = v2
                        elif n2 in (3, 4):
                            number = v2
                    if sid == tf_op:
                        op = _text(buf, text) if text is not None \
                            else stats.get(ref, "")
                    elif sid == program_id:
                        prog = number
            if prog is not None:  # an operation of a program
                table[prog, own] = (op, hlo)
    return out


# ---- from a name stack to a scope ------------------------------------------

def scope_of(tf_op) -> str:
    """The innermost component of a `tf_op` path that `GROUPS` knows;
    `while` for the loop's own operations where there is none."""
    parts = tf_op.rstrip(":").split("/") if tf_op else [""]
    for part in reversed(parts):
        if part in _NAMED:
            return part
    return LOOP if parts[-1] == LOOP else UNSCOPED


def program_id(module_name: str):
    """`jit_engine_decode(4270621360859558152)` -> 4270621360859558152."""
    head, _, tail = module_name.rpartition("(")
    tail = tail.rstrip(")")
    return int(tail) if head and tail.isdigit() else None


@dataclasses.dataclass
class Op:
    """One operation of a device's `XLA Ops` line, placed."""
    name: str  # its own name: `fusion.16`
    kind: str  # `fusion`: one row a kind of operation, as `breakdown` has
    self_s: float  # seconds not covered by the operations nested in it
    dur: float
    program: str  # the jit it ran in (`jit_engine_decode`), "" for none
    execution: int  # the index of that execution among the plane's
    scope: str  # a name of `GROUPS`
    kernel: bool
    tf_op: str
    hlo: str  # the whole instruction: its shapes and layouts


def self_times(events) -> list:
    """(event, seconds no later operation covers), as `xplane._self_times`:
    a `while` keeps only what its body does not cover. One thing more: XLA
    starts an operation before the one before it has ended (the head of a
    kernel beside the tail of a fusion, a `slice-done` beside a copy: some
    300 times in a quarter of a second of Laguna's trace), and such an
    OVERHANG lies in the enclosing `while` too. Each piece of an operation
    is taken off the innermost operation that covers it, so the self times
    of a line sum to the union of its intervals (`xplane._self_times` takes
    the overhang off nothing, and its `while` keeps it: up to 5% of a
    step)."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].start + stack[-1][0].dur <= e.start:
            out.append(tuple(stack.pop()))
        at, end = e.start, e.start + e.dur
        for top in reversed(stack):
            top_end = top[0].start + top[0].dur
            if top_end > at:
                top[1] -= min(end, top_end) - at
                at = min(end, top_end)
                if at >= end:
                    break
        stack.append([e, e.dur])
    out.extend(tuple(s) for s in stack)
    return out


def executions(loaded: xplane.Loaded) -> list:
    """Every program execution of every device plane, as the `XLA Modules`
    lines have them: (plane, the jit's name, start, end, program id), a
    plane after the other, each in time order."""
    return [(plane, m.name.split("(")[0], m.start, m.start + m.dur,
             program_id(m.name))
            for plane, mods in loaded.modules.items()
            for m in sorted(mods, key=lambda m: m.start)]


def place(loaded: xplane.Loaded, metadata: dict):
    """Every operation of every device plane as an `Op`: the ONE piece of
    code that puts an operation into a program. It belongs to the execution
    it starts in; `Op.execution` counts into `executions(loaded)`."""
    execs = executions(loaded)
    for plane, events in loaded.ops.items():
        mine = [i for i, x in enumerate(execs) if x[0] == plane]
        starts = [execs[i][2] for i in mine]
        table = metadata.get(plane, {})
        for e, own in self_times(events):
            i = bisect.bisect_right(starts, e.start) - 1
            at, program, tf_op, hlo = -1, "", "", ""
            if i >= 0 and e.start < execs[mine[i]][3]:
                at = mine[i]
                program = execs[at][1]
                tf_op, hlo = table.get((execs[at][4], e.name), ("", ""))
            kind = xplane._base(e.name)
            scope = scope_of(tf_op)
            if scope == UNSCOPED and kind == LOOP:
                scope = LOOP
            yield Op(e.name, kind, own, e.dur, program, at, scope,
                     kind in KERNELS, tf_op, hlo)


# ---- the account of a traced run -------------------------------------------

@dataclasses.dataclass
class Row:
    """One scope's device time in one program, summed over executions:
    seconds by kind of operation, the Mosaic kernels apart from XLA's."""
    kernels: dict = dataclasses.field(default_factory=dict)
    xla: dict = dataclasses.field(default_factory=dict)

    @property
    def kernel_s(self) -> float:
        return sum(self.kernels.values())

    @property
    def xla_s(self) -> float:
        return sum(self.xla.values())


@dataclasses.dataclass
class Account:
    """Device time by scope of the executions that lie WHOLLY inside the
    traced window (`Reduced.kernel_in_program`'s rule)."""
    n: dict  # program -> executions counted
    rows: dict  # program -> {scope: Row}
    busy_s: dict  # program -> device busy seconds of those executions:
    # the union of the operations' intervals inside them, as `Reduced` has it
    names: int  # operations whose metadata carried a name stack
    old_names: bool  # the trace carries the names of a tree before PR 52

    def xla_ms(self, program: str):
        """Self time of every operation that is no kernel, any scope, in
        ms an execution."""
        if not self.n.get(program):
            return None
        return sum(r.xla_s for r in self.rows[program].values()
                   ) * 1e3 / self.n[program]

    def group_ms(self, program: str, group: str):
        """ALL device time of a group's scopes, kernels included, in ms
        an execution. The six groups sum to the executions' busy time."""
        if not self.n.get(program):
            return None
        return sum(r.kernel_s + r.xla_s
                   for s, r in self.rows[program].items()
                   if GROUP_OF[s] == group) * 1e3 / self.n[program]

    def lines(self) -> list:
        """The table an engineer reads: a program, then a row a scope."""
        out = []
        for program, rows in self.rows.items():
            n = self.n[program]
            if not n:
                continue
            total = sum(r.kernel_s + r.xla_s for r in rows.values())
            busy = self.busy_s[program]
            out.append(
                f"device time by scope, {program}: {n} executions whole "
                f"in the traced window, busy {busy * 1e3 / n:.3f} ms each, "
                f"the rows sum to {total * 1e3 / n:.3f}; ms an execution:")
            out.append(f"  {'scope':24s} {'group':9s} {'kernel':>8s} "
                       f"{'XLA':>8s}   largest kinds")
            order = [s for scopes in GROUPS.values() for s in scopes]
            for s in sorted(rows, key=order.index):
                r = rows[s]
                if not (r.kernel_s or r.xla_s):
                    continue
                kinds = ", ".join(f"{k} {v * 1e3 / n:.3f}" for k, v in
                                  sorted(r.kernels.items(),
                                         key=lambda kv: -kv[1])[:2]
                                  + sorted(r.xla.items(),
                                           key=lambda kv: -kv[1])[:2])
                out.append(f"  {s:24s} {GROUP_OF[s]:9s} "
                           f"{r.kernel_s * 1e3 / n:8.3f} "
                           f"{r.xla_s * 1e3 / n:8.3f}   {kinds}")
        if self.old_names:
            out.append(
                "  the trace carries `norm_rope`, a name of the tree before "
                "PR 52: an older tree, or programs loaded from a compile "
                "cache that one filled (its key leaves the metadata out)")
        return out


def _covered(intervals, a: float, b: float) -> float:
    """Seconds of a..b that the disjoint sorted `intervals` cover."""
    i = bisect.bisect_right(intervals, (a, float("inf"))) - 1
    out = 0.0
    for lo, hi in intervals[max(i, 0):]:
        if lo >= b:
            break
        out += max(min(hi, b) - max(lo, a), 0.0)
    return out


def build(dev: xplane.Reduced, metadata: dict) -> Account:
    execs = executions(dev.loaded)
    whole = {i for i, (_, _, a, b, _) in enumerate(execs)
             if dev.begin <= a and b <= dev.end}
    n, rows, busy = {}, {}, {}
    for p in PROGRAMS:
        mine = [execs[i] for i in whole if p in execs[i][1]]
        n[p], rows[p] = len(mine), {}
        busy[p] = sum(_covered(dev._busy.get(plane, ()), a, b)
                      for plane, _, a, b, _ in mine)
    named, old = 0, False
    for op in place(dev.loaded, metadata):
        named += bool(op.tf_op)
        old = old or "norm_rope" in op.tf_op
        if op.execution not in whole:
            continue
        p = next((p for p in PROGRAMS if p in op.program), None)
        if p is None:
            continue
        row = rows[p].setdefault(op.scope, Row())
        kinds = row.kernels if op.kernel else row.xla
        kinds[op.kind] = kinds.get(op.kind, 0.0) + op.self_s
    return Account(n=n, rows=rows, busy_s=busy, names=named, old_names=old)


def account(run) -> Account | None:
    """The run's account, built once: the eight readers share it. The
    operations are the ones `bench/run.py` reduced (`run.device`); their
    name stacks are `run.extra["scope_metadata"]` where a run brings its
    own, else read from the trace where `bench/run.py` had it written. The
    table is printed with the run's log. Never raises: whatever goes wrong
    is ONE printed line, and None."""
    if KEY not in run.extra:
        acc, t = None, time.perf_counter()
        try:
            if run.device is None:
                raise LookupError("no device trace")
            metadata = run.extra.get("scope_metadata")
            if metadata is None:
                metadata = read_metadata(xplane.find_trace(
                    os.path.join(run.cell.root, ".bench_trace")))
            acc = build(run.device, metadata)
            if not acc.names:
                raise LookupError(
                    "no operation of the trace carries a `tf_op`")
            print("\n".join(acc.lines() + [
                f"  (the name stacks read and the operations placed in "
                f"{time.perf_counter() - t:.1f} s, after the window)"]),
                flush=True)
        except Exception as e:  # a reader returns None and the line
            # leaves the metric out; a traced run must not die of it
            print(f"device time by scope: not read "
                  f"({type(e).__name__}: {e})", flush=True)
            acc = None
        run.extra[KEY] = acc
    return run.extra[KEY]
