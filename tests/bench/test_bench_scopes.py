"""bench/reduce/scopes.py and the eight readers on it (`step.xla_ms`,
`step.scope.*_ms`, `step.prefill.xla_ms`): device time by scope, on
hand-built traces (operations at known times under known name stacks, a
profile's bytes written out by hand), then on a quarter of a second of a
trace recorded on the chip (bench/fixtures/v5e_scopes.json.gz, cut by
bench/tools/keep_scopes.py from a traced run of
`laguna-xs.2.mixedlen-closed`)."""

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells  # noqa: E402
from bench.records import Run  # noqa: E402
from bench.reduce import scopes  # noqa: E402
from bench.reduce.xplane import Event, Loaded, Reduced  # noqa: E402
from bench.tools import keep_scopes  # noqa: E402

CELL = "laguna-xs.2.mixedlen-closed"
DEV = "/device:TPU:0"
DECODE, PREFILL = "jit_engine_decode(11)", "jit_engine_paged_prefill(22)"
GROUPS = tuple(scopes.GROUPS)
READERS = ("step.xla_ms", "step.prefill.xla_ms") + tuple(
    f"step.scope.{g}_ms" for g in GROUPS)
CLOSED = (
    "qwen2-7b.chat-closed", "mistral-7b.longprompt-closed",
    "mixtral-8x7b.chat-closed", "brumby-14b.reason-closed",
    "glm-4.7-flash.longctx-closed", "granite-4.0-h-small.concurrent-closed",
    "smallthinker-21ba3b.mixedlen-closed", "laguna-xs.2.mixedlen-closed",
    "sdar-30b-a3b.blockgen-closed")
MS = 1e-3


@pytest.fixture(scope="module")
def cell():
    return cells.resolve(CELL, ROOT)


@pytest.fixture(scope="module")
def kept():
    path = os.path.join(ROOT, "bench", "fixtures", "v5e_scopes.json.gz")
    with gzip.open(path, "rt", encoding="utf-8") as f:
        return json.load(f)


def make_run(cell, modules, ops, names, end=1.0):
    """A traced run whose device ran `modules` (name, start, dur) and `ops`
    (name, start, dur); `names`: {(program id, own name): tf_op}, or None
    for a profile that carries no name stack."""
    ld = Loaded({DEV: [Event(*o) for o in ops]},
                {DEV: [Event(*m) for m in modules]}, sync=0.0, lines={})
    dev = Reduced(ld, t_sync=0.0, begin=0.0, end=end)
    meta = {} if names is None else {
        DEV: {k: (v, "") for k, v in names.items()}}
    return Run(cell=cell, hf={}, peak={}, t0=0.0, t1=1e9, requests=[],
               device=dev, extra={"scope_metadata": meta})


def read(cell, run) -> dict:
    return {name: cell.reader(name).read(run) for name in READERS}


# one decode step of 10 ms: a layer loop that holds a kernel under
# `attn.proj`, a fusion under `norm`, one the compiler made for the loop, one
# that names nothing, and a kernel that starts before the fusion before it
# has ended; then the head; 1 ms idle at the end
STEP = [
    ("while.1", 0 * MS, 8 * MS),
    ("qmatmul.7", 0.5 * MS, 2 * MS),
    ("fusion.3", 3 * MS, 1 * MS),
    ("qmatmul.8", 3.5 * MS, 1 * MS),  # overhangs fusion.3 by 0.5 ms
    ("copy.2", 5 * MS, 1 * MS),
    ("fusion.9", 6.5 * MS, 0.5 * MS),
    ("qmatmul.9", 8 * MS, 1 * MS),
]
NAMES = {
    (11, "while.1"): "jit(engine_decode)/while:",
    (11, "qmatmul.7"): "jit(engine_decode)/while/body/closed_call/attn/"
                       "attn.proj/jit(_qmm)/qmatmul/pallas_call:",
    (11, "fusion.3"): "jit(engine_decode)/while/body/closed_call/norm/mul:",
    (11, "qmatmul.8"): "jit(engine_decode)/while/body/closed_call/ffn/"
                       "jit(_qmm)/qmatmul/pallas_call:",
    (11, "copy.2"): "jit(engine_decode)/while:",
    (11, "fusion.9"): "jit(engine_decode)/while/body/closed_call/add:",
    (11, "qmatmul.9"): "jit(engine_decode)/lm_head/lm_head/jit(_qmm)/"
                       "qmatmul/pallas_call:",
}


def test_a_step_is_split_by_scope_and_a_nested_while_counts_once(cell):
    run = make_run(cell, [(DECODE, 0.0, 10 * MS)], STEP, NAMES)
    got = read(cell, run)
    # kernels 2 + 1 + 1; XLA: the fusion less its overhang, the loop's copy,
    # the unnamed fusion, and the while's own 8 - 2 - 1.5 - 1 - 0.5 = 3.0
    # (its body's 5 ms taken off once, the overhang past fusion.3 too)
    assert got["step.xla_ms"] == pytest.approx(0.5 + 1 + 0.5 + 3.0)
    assert got["step.scope.mixer_ms"] == pytest.approx(2.0)
    assert got["step.scope.ffn_ms"] == pytest.approx(1.0)
    assert got["step.scope.norm_ms"] == pytest.approx(0.5)
    assert got["step.scope.head_ms"] == pytest.approx(1.0)
    assert got["step.scope.engine_ms"] == pytest.approx(3.0 + 1.0)
    assert got["step.scope.unscoped_ms"] == pytest.approx(0.5)
    assert sum(got[f"step.scope.{g}_ms"] for g in GROUPS) == \
        pytest.approx(9.0)  # the union of the intervals: busy, not 10
    assert got["step.prefill.xla_ms"] is None  # no prefill ran
    acc = scopes.account(run)
    assert acc.busy_s["engine_decode"] == pytest.approx(9 * MS)
    row = acc.rows["engine_decode"]["while"]
    assert row.xla == pytest.approx({"while": 3 * MS, "copy": 1 * MS})


def test_only_executions_whole_in_the_window_count_and_each_program_apart(
        cell):
    mods = [(DECODE, 0.0, 10 * MS), (PREFILL, 20 * MS, 10 * MS),
            (DECODE, 40 * MS, 10 * MS), (DECODE, 95 * MS, 10 * MS)]
    ops = STEP + [("copy.1", 21 * MS, 4 * MS), ("flash_attention.2",
                                               25 * MS, 5 * MS)]
    ops += [(n, a + 40 * MS, d) for n, a, d in STEP]
    ops += [(n, a + 95 * MS, d) for n, a, d in STEP]  # cut by the window
    names = dict(NAMES)
    names[22, "copy.1"] = "jit(engine_paged_prefill)/engine/gather:"
    names[22, "flash_attention.2"] = "jit(engine_paged_prefill)/attn/" \
        "flash_attention/pallas_call:"
    run = make_run(cell, mods, ops, names, end=0.1)
    acc = scopes.account(run)
    assert acc.n == {"engine_decode": 2, "engine_paged_prefill": 1,
                     "generate_tokens": 0}
    got = read(cell, run)
    assert got["step.xla_ms"] == pytest.approx(5.0)  # a step's, not two
    assert got["step.prefill.xla_ms"] == pytest.approx(4.0)
    assert acc.group_ms("engine_paged_prefill", "mixer") == \
        pytest.approx(5.0)
    assert acc.group_ms("engine_paged_prefill", "engine") == \
        pytest.approx(4.0)


def test_the_names_of_the_tree_before_pr_52_read_too(cell, capsys):
    names = dict(NAMES)
    names[11, "fusion.3"] = \
        "jit(engine_decode)/while/body/closed_call/norm_rope/mul:"
    run = make_run(cell, [(DECODE, 0.0, 10 * MS)], STEP, names)
    assert read(cell, run)["step.scope.norm_ms"] == pytest.approx(0.5)
    assert scopes.account(run).old_names
    assert "a name of the tree before PR 52" in capsys.readouterr().out


@pytest.mark.parametrize("what", ["no_tf_op", "no_decode", "no_trace",
                                  "no_file", "bad_bytes"])
def test_a_trace_the_reducer_cannot_read_gives_none_and_one_line(
        cell, capsys, tmp_path, what):
    run = make_run(cell, [(DECODE, 0.0, 10 * MS)], STEP, NAMES)
    if what == "no_tf_op":
        run = make_run(cell, [(DECODE, 0.0, 10 * MS)], STEP, None)
    elif what == "no_decode":  # `generate_tokens` ran, and is printed
        run = make_run(cell, [("jit_generate_tokens(11)", 0.0, 10 * MS)],
                       STEP, NAMES)
    elif what == "no_trace":
        run.device = None
    else:  # the profile's own file: none there, or no protobuf
        del run.extra["scope_metadata"]
        run.cell = cells.resolve(CELL, ROOT)
        run.cell.root = str(tmp_path)
        if what == "bad_bytes":
            d = tmp_path / ".bench_trace" / "plugins" / "profile" / "x"
            d.mkdir(parents=True)
            (d / "vm.xplane.pb").write_bytes(b"\x0a\xff\xff\xff\xff\x7f!")
    assert all(v is None for v in read(cell, run).values())
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("device time by scope")]
    assert len(lines) == 1, lines  # eight readers, one line
    assert ("generate_tokens: 1 executions" if what == "no_decode"
            else "not read") in lines[0]


# ---- the wire format: a profile's bytes written out by hand ---------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num: int, payload) -> bytes:
    if isinstance(payload, int):
        return _varint(num << 3) + _varint(payload)
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _plane(name: str, stat_names: dict, events: list) -> bytes:
    """An XPlane with a line to skip, `stat_metadata` and `event_metadata`
    (own name, whole instruction, [(stat id, value)])."""
    out = _field(1, 7) + _field(2, name)
    out += _field(3, _field(2, "XLA Ops") + _field(4, _field(1, 1)
                                                   + _field(3, 12345)))
    for sid, sname in stat_names.items():
        out += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                 + _field(2, sname)))
    for i, (own, hlo, stats) in enumerate(events, 1):
        body = _field(1, i) + _field(2, hlo) + _field(4, own)
        for sid, value in stats:
            body += _field(5, _field(1, sid) + (
                _field(5, value) if isinstance(value, str) else
                _field(7, -value) if value < 0 else _field(3, value)))
        out += _field(4, _field(1, i) + _field(2, body))
    return out


def test_the_metadata_is_read_from_the_profiles_own_bytes(tmp_path):
    big = 11937236725742203718  # past 63 bits, as a program's id is
    stats = {3: "program_id", 9: "tf_op", 12: "jit(f)/norm/mul:", 4: "flops"}
    space = _field(1, _plane(
        "/device:TPU:0", stats, [
            ("fusion.16", "%fusion.16 = f32[8]{0} fusion(%p)", [
                (3, big), (4, 99), (9, "jit(engine_decode)/attn/add:")]),
            ("copy.1", "%copy.1 = f32[8]{0} copy(%p)", [
                (9, -12), (3, big)]),  # a name stack kept by reference
            ("bare.2", "%bare.2 = f32[] constant(0)", [(3, big)])]))
    space += _field(1, _plane("/host:CPU", stats, [
        ("python", "python", [(9, "not a device's")])]))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert scopes.read_metadata(str(path)) == {"/device:TPU:0": {
        (big, "fusion.16"): ("jit(engine_decode)/attn/add:",
                             "%fusion.16 = f32[8]{0} fusion(%p)"),
        (big, "copy.1"): ("jit(f)/norm/mul:", "%copy.1 = f32[8]{0} copy(%p)"),
        (big, "bare.2"): ("", "%bare.2 = f32[] constant(0)"),  # no name
    }}
    path.write_bytes(space[:-3])  # a file cut short is an error, and
    with pytest.raises((ValueError, IndexError)):  # `account` catches it
        scopes.read_metadata(str(path))


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(engine_decode)/while/body/closed_call/ffn/moe.router/top_k:",
     "moe.router"),
    ("jit(engine_decode)/attn.proj/jit(_qmm)/qmatmul/pallas_call:",
     "attn.proj"),
    ("jit(engine_decode)/while/body/closed_call/norm_rope/mul:",
     "norm_rope"),
    ("jit(engine_paged_prefill)/while:", "while"),
    ("jit(engine_decode)/jit(norm)/mul:", "unscoped"),  # a jit, no scope
    ("", "unscoped"), (None, "unscoped"),
])
def test_a_name_stack_gives_its_innermost_scope(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope
    assert scope in scopes.GROUP_OF


def test_every_scope_of_the_program_is_in_exactly_one_group():
    from bigdl_tpu.obs.scopes import VOCABULARY

    listed = [s for names in scopes.GROUPS.values() for s in names]
    assert len(listed) == len(set(listed))
    assert set(VOCABULARY) == set(listed) - {"norm_rope", "while",
                                             "unscoped"}
    assert scopes.GROUPS["unscoped"] == ("unscoped",)


# ---- the recorded trace -----------------------------------------------------

def test_the_recorded_steps_groups_sum_to_their_busy_time(cell, kept):
    dev, meta = keep_scopes.reduced(kept)
    run = Run(cell=cell, hf={}, peak={}, t0=0.0, t1=1e9, requests=[],
              device=dev, extra={"scope_metadata": meta})
    acc = scopes.account(run)
    assert acc is not None and not acc.old_names
    expect = kept["expect"]
    assert set(expect) == {"engine_decode", "engine_paged_prefill"}
    for program, want in expect.items():
        assert acc.n[program] == want["n"] >= 1
        total = sum(acc.group_ms(program, g) for g in GROUPS)
        assert total == pytest.approx(want["busy_ms"], rel=0.01)
        assert acc.xla_ms(program) == pytest.approx(want["xla_ms"])
        for g in GROUPS:
            assert acc.group_ms(program, g) == pytest.approx(want[g])
    got = read(cell, run)
    step = expect["engine_decode"]
    assert got["step.xla_ms"] == pytest.approx(step["xla_ms"])
    assert got["step.prefill.xla_ms"] == pytest.approx(
        expect["engine_paged_prefill"]["xla_ms"])
    # the tree's own names: next to nothing of a decode step is unscoped,
    # the kernels are most of it, and each row's time is the scope's own
    assert got["step.scope.unscoped_ms"] < 0.02 * step["busy_ms"]
    assert 0 < got["step.xla_ms"] < 0.3 * step["busy_ms"]
    rows = acc.rows["engine_decode"]
    assert set(rows["moe.experts"].kernels) == {"moe_qmatmul"}
    assert set(rows["attn"].kernels) == {"paged_decode_attention"}
    assert set(rows["attn.proj"].kernels) == {"qmatmul"}
    assert not rows["norm"].kernels and rows["norm"].xla_s > 0


def test_the_recorded_loop_keeps_only_what_its_body_does_not_cover(kept):
    dev, meta = keep_scopes.reduced(kept)
    ops = [op for op in scopes.place(dev.loaded, meta)
           if "engine_decode" in op.program]
    loops = [op for op in ops if op.kind == "while"]
    assert loops and all(op.scope == "while" for op in loops)
    # the loop spans most of its step (the first period of Laguna's layers
    # stands before it) and keeps microseconds of it
    assert sum(op.dur for op in loops) > 0.5 * sum(op.self_s for op in ops)
    assert sum(op.self_s for op in loops) < 0.01 * sum(op.dur for op in loops)


# ---- BENCHMARK.json ---------------------------------------------------------

def test_the_fifteen_entries_are_listed_for_their_cells_and_load():
    bench = cells.load_benchmark(ROOT)
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"].split("--")[0] in READERS}
    assert len(mine) == 15
    for name, m in mine.items():
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "ms", "lower", "device_trace", "model step")
        if name == "step.prefill.xla_ms":
            assert m["moves"] == "ttft_ms_p90"
            assert m["workloads"] == ["mistral-7b.longprompt-closed"]
        elif name.endswith("--closed"):
            assert m["moves"] == "output_tokens_per_s"
            assert tuple(m["workloads"]) == CLOSED
        else:
            assert m["moves"] == "itl_ms_p95"
            assert m["workloads"] == ["mistral-7b.chat-steady"]
    for w in bench["workloads"]:
        c = cells.resolve(w["name"], ROOT)
        for m in c.per_layer:
            if m["name"] in mine:
                reader = c.reader(m["name"])
                assert c.entry_name in reader.ENTRIES and callable(reader.read)
    assert not any(m["name"] in mine for m in cells.resolve(
        "mistral-7b.generate-1024-128", ROOT).per_layer)
