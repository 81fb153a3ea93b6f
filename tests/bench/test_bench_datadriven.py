"""The harness is driven by data: a new cell and a new per-layer metric are
new files and new entries, and no edit to a file that is there. And
BENCHMARK.json keeps to the contract's form."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import cells  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark(ROOT)


def test_benchmark_json_has_exactly_the_contracts_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench", "tests/bench"]
    assert bench["command"] == ["python3", "bench/run.py"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_entry_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] == 1 and 1 <= len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and "\n" not in m["layer"]
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for group in (bench["configs"], bench["workloads"], metrics):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_cell_and_metric_hangs_together(bench):
    cell_names = [w["name"] for w in bench["workloads"]]
    assert {w["config"] for w in bench["workloads"]} == \
        {c["name"] for c in bench["configs"]}  # every config has a cell
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]

    def reported_in(m):
        return set(m.get("workloads", cell_names))

    for m in bench["end_to_end"] + bench["per_layer"]:
        assert reported_in(m) <= set(cell_names), m["name"]
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert reported_in(m) <= reported_in(e2e[m["moves"]]), m["name"]
    for name in cell_names:
        cell = cells.resolve(name, ROOT)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "entries", cell.entry_name + ".py"))
        for m in cell.end_to_end + cell.per_layer:
            reader = cell.reader(m["name"])
            assert callable(reader.read)
            # a per-layer metric lists the entry kinds whose runs carry its
            # source; the cell's entry must be one of them
            assert cell.entry_name in getattr(reader, "ENTRIES",
                                              (cell.entry_name,)), m["name"]


# what `reduced` may never name: a width (the contract's list), and here also
# the head counts and the vocabulary, which set projection sizes
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head|expan|experts_per_tok|vocab")


def check_config(root, entry):
    """A configuration file against its OWN `published` block (the source's
    config.json keys, copied): every key runs at its published value unless
    `reduced` names it (a cut, never of a width) or `assumed` says why it
    runs otherwise. No size is written in this test."""
    cfg = cells.load_json(root, entry["file"])
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    run, pub = cells.as_run(cfg), cfg["published"]
    assert pub, "no published keys to hold the file to"
    for key, value in pub.items():
        if key in cfg["reduced"]:
            assert run[key] != value, f"{key} is listed as reduced and is not"
        elif key in cfg.get("assumed", {}):
            assert cfg["assumed"][key]  # the reason
        else:
            assert key in run and run[key] == value, key
    assert set(run) <= set(pub), "a key runs that the source does not have"
    assert set(cfg["reduced"]) <= set(pub)
    assert not [k for k in cfg["reduced"] if WIDTH.search(k)]
    assert set(cfg["bench"]) >= {"deployment", "qtype", "reference",
                                 "tolerances", "rehearsal"}
    for tol in cfg["bench"]["tolerances"].values():
        assert tol["why"] and 0 < tol["value"] < float("inf")
    # the correctness check bounds the WORST token or logit, not an average
    assert set(cfg["bench"]["tolerances"]) & {"logprob_atol_nats",
                                              "greedy_gap_atol"}


def _configs():
    return cells.load_benchmark(ROOT)["configs"]


@pytest.mark.parametrize("entry", _configs(), ids=lambda e: e["name"])
def test_configs_state_their_cut(entry):
    check_config(ROOT, entry)


def test_a_cut_width_or_an_unexplained_key_is_caught(tmp_path):
    entry = _configs()[0]
    cfg = cells.load_json(ROOT, entry["file"])
    os.makedirs(tmp_path / "bench" / "configs")

    def write(changed, reduced=()):
        c = dict(cfg, **changed, reduced=list(reduced))
        with open(tmp_path / entry["file"], "w") as f:
            json.dump(c, f)
        return dict(entry, reduced=list(reduced))

    check_config(str(tmp_path), write({}))
    with pytest.raises(AssertionError):  # differs and nothing says so
        check_config(str(tmp_path), write({"num_hidden_layers": 2}))
    check_config(str(tmp_path), write({"num_hidden_layers": 2},
                                      ["num_hidden_layers"]))
    with pytest.raises(AssertionError):  # a width may never be cut
        check_config(str(tmp_path), write({"hidden_size": 64},
                                          ["hidden_size"]))
    with pytest.raises(AssertionError):  # listed as cut, and is not
        check_config(str(tmp_path), write({}, ["num_hidden_layers"]))


NEW_CELL = "tinyllama.chat-busy"
OTHER_CONFIG = json.dumps({
    "source": "https://huggingface.co/TinyLlama/TinyLlama-1.1B-Chat-v1.0/"
              "blob/main/config.json",
    "model_type": "llama", "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "max_position_embeddings": 2048,
    "num_attention_heads": 32, "num_hidden_layers": 11,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "vocab_size": 32000,
    "published": {
        "model_type": "llama", "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 2048,
        "num_attention_heads": 32, "num_hidden_layers": 22,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-05,
        "rope_theta": 10000.0, "tie_word_embeddings": False,
        "vocab_size": 32000},
    "reduced": ["num_hidden_layers"],
    "assumed": {},
    "bench": {
        "deployment": "a test's", "qtype": "sym_int4", "reference": "mistral",
        "engine": {"n_slots": 8, "max_len": 2048, "page_size": 64,
                   "n_pages": 257},
        "tolerances": {"logprob_atol_nats": {"value": 0.5, "why": "a test"}},
        "rehearsal": {
            "hidden_size": 256, "intermediate_size": 512,
            "num_hidden_layers": 1, "num_attention_heads": 4,
            "num_key_value_heads": 2, "vocab_size": 512,
            "bench": {"engine": {"n_slots": 4, "max_len": 256,
                                 "n_pages": 17}}}}})

NEW_METRIC = '''"""Requests attempted in the window (added by a test)."""

ENTRIES = ("engine",)


def read(run):
    return float(len(run.requests))
'''


def test_a_new_cell_and_metric_are_new_files_and_entries_only(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "bigdl_tpu"), tmp_path / "bigdl_tpu")
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(tmp_path / "bench") for p in fs}

    # one config file (another model, other widths), one traffic file, one
    # metric file ...
    cfg = json.loads(OTHER_CONFIG)
    with open(tmp_path / "bench" / "configs" / "tinyllama.json", "w") as f:
        json.dump(cfg, f)
    traffic = cells.load_json(root, "bench", "traffic", "chat-steady.json")
    traffic["process"] = {"kind": "poisson", "rate_rps": 2.5}
    traffic["rehearsal"]["process"] = {"rate_rps": 3.0}
    with open(tmp_path / "bench" / "traffic" / "chat-busy.json", "w") as f:
        json.dump(traffic, f)
    (tmp_path / "bench" / "metrics" / "loadgen.attempted.py").write_text(
        NEW_METRIC)
    # ... and entries in BENCHMARK.json
    b = cells.load_benchmark(root)
    entry = {"name": "tinyllama", "source": cfg["source"],
             "file": "bench/configs/tinyllama.json",
             "reduced": ["num_hidden_layers"], "why": "a test"}
    b["configs"].append(entry)
    b["workloads"].append({"name": NEW_CELL, "config": "tinyllama",
                           "traffic": "chat-busy", "chips": 1,
                           "why": "a test"})
    for m in b["end_to_end"]:
        if m["name"] in ("ttft_ms_p90", "itl_ms_p95"):
            m["workloads"].append(NEW_CELL)
    for m in b["per_layer"]:
        if m["name"] == "engine.queue_wait_ms_p50":
            m["workloads"].append(NEW_CELL)
    b["per_layer"].append({
        "name": "loadgen.attempted", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "ttft_ms_p90", "workloads": [NEW_CELL]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(b, f)

    check_config(root, entry)  # the same check, other widths, no edit
    cell = cells.resolve(NEW_CELL, root)
    assert cell.config["hidden_size"] == 2048
    assert [m["name"] for m in cell.end_to_end] == [
        "ttft_ms_p90", "itl_ms_p95", "setup_s"]
    assert "loadgen.attempted" in [m["name"] for m in cell.per_layer]

    # the copy's own run.py drives the new cell and reports the new metric
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), "--workload",
         NEW_CELL, "--seed", "5", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 3, r.stderr[-3000:]
    last = r.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL complete, not a result: ")
    result = json.loads(last.split(": ", 1)[1])
    assert result["correct"] and result["attempted"] > 0
    assert result["metrics"]["loadgen.attempted"]["value"] == \
        result["attempted"]
    assert "engine.queue_wait_ms_p50" in result["metrics"]
    assert result["device"]["platform"] == "cpu"  # never a device it did
    # not run on

    # nothing that was there was edited
    after = {p: open(os.path.join(dp, p), "rb").read()
             for dp, _, fs in os.walk(tmp_path / "bench") for p in fs
             if "__pycache__" not in dp and ".bench_trace" not in dp}
    assert all(after[p] == data for p, data in before.items())
