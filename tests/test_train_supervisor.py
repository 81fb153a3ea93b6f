"""Chaos matrix for the training supervisor (train/supervisor.py) and
the multi-host health layer (parallel/health.py).

Acceptance invariants (ISSUE 10): for every TrainFaultInjector point
the supervised loop either skips-and-continues (anomaly), resumes
bit-exactly after a simulated preemption + restart, or aborts with a
structured diagnostic (watchdog / rank_drop) — never a silent hang —
and the final params of an injected run with skips equal a clean run
minus exactly the skipped steps.

Most scenarios run on a millisecond-scale toy problem (the supervisor
is train-step-agnostic by contract); one integration case drives the
real QLoRA step on the dryrun multihost mesh (8 virtual CPU devices),
and one real-SIGTERM case exercises the signal path in a subprocess.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time
import zipfile

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bigdl_tpu.parallel.health import (
    HealthMonitor,
    RankDropError,
    anomaly_consensus,
    init_multihost_with_retry,
)
from bigdl_tpu.serving import metrics as M
from bigdl_tpu.train.checkpoint import (
    list_train_checkpoints,
    load_latest_train_state,
    save_train_state_rotating,
)
from bigdl_tpu.train.supervisor import (
    EXIT_PREEMPTED,
    EventLog,
    SupervisorAbort,
    SupervisorConfig,
    TrainFaultInjector,
    TrainSupervisor,
)

pytestmark = pytest.mark.chaos


# ---------------------------------------------------------------------------
# toy harness: a deterministic-by-step quadratic problem — exact
# equality between a supervised run and a manual replay is meaningful
# ---------------------------------------------------------------------------

def _toy(lr=0.2):
    opt = optax.sgd(lr)
    lora0 = {"layers": {"w": jnp.zeros((4,), jnp.float32)},
             "scale": jnp.asarray(1.0, jnp.float32)}
    opt_state0 = opt.init(lora0["layers"])

    def step_fn(lora, opt_state, target):
        def loss_fn(layers):
            return jnp.sum((layers["w"] - target) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(lora["layers"])
        updates, opt_state = opt.update(g, opt_state, lora["layers"])
        layers = optax.apply_updates(lora["layers"], updates)
        return ({"layers": layers, "scale": lora["scale"]}, opt_state,
                loss, optax.global_norm(g))

    def batch_fn(step):
        return (jnp.full((4,), float(step % 3 + 1), jnp.float32),)

    return step_fn, batch_fn, lora0, opt_state0


def _manual(step_fn, batch_fn, lora, opt_state, steps):
    """Ground truth: apply exactly `steps` (an iterable of indices)."""
    for s in steps:
        lora, opt_state, _, _ = step_fn(lora, opt_state, *batch_fn(s))
    return lora, opt_state


def _w(lora):
    return np.asarray(lora["layers"]["w"])


def _sup(tmp_path, step_fn, lora0, opt_state0, *, faults=None, **cfg):
    defaults = dict(save_every=100, warmup_steps=2, heartbeat_every=0)
    defaults.update(cfg)
    return TrainSupervisor(
        step_fn, ckpt_dir=str(tmp_path), lora=lora0, opt_state=opt_state0,
        rng=jax.random.PRNGKey(0), config=SupervisorConfig(**defaults),
        faults=faults,
    )


def _events(tmp_path):
    return EventLog.tail(str(tmp_path / "supervisor_events.jsonl"), n=100)


# ---------------------------------------------------------------------------
# clean path
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_clean_run_checkpoints_and_matches_manual(tmp_path):
    step_fn, batch_fn, lora0, opt0 = _toy()
    sup = _sup(tmp_path, step_fn, lora0, opt0, save_every=2)
    assert sup.resume() == 0
    out = sup.run(batch_fn, 6)
    assert out["step"] == 6
    ref_lora, ref_opt = _manual(step_fn, batch_fn, lora0, opt0, range(6))
    np.testing.assert_array_equal(_w(out["lora"]), _w(ref_lora))
    # rotation: keep_last=3 of {0,2,4,6}
    steps = [p[-12:-4] for p in list_train_checkpoints(str(tmp_path))]
    assert steps == ["00000006", "00000004", "00000002"]
    kinds = [e["kind"] for e in _events(tmp_path)]
    assert "checkpoint" in kinds and "anomaly" not in kinds


# ---------------------------------------------------------------------------
# anomaly skips: every guard, optimizer state untouched, exact
# clean-minus-skipped parity
# ---------------------------------------------------------------------------

@pytest.mark.core
@pytest.mark.parametrize("point,reason", [
    ("nan_loss", "nan_loss"),
    ("nan_grad", "nan_grad"),
    ("loss_spike", "loss_spike"),
])
def test_anomaly_skips_and_matches_clean_minus_skipped(
        tmp_path, point, reason):
    step_fn, batch_fn, lora0, opt0 = _toy()
    inj = TrainFaultInjector(seed=0).arm(point, times=1, after=3)
    before = M.TRAIN_STEPS_SKIPPED.value
    sup = _sup(tmp_path, step_fn, lora0, opt0, faults=inj)
    sup.resume()
    reports = []
    out = sup.run(batch_fn, 6, on_step=reports.append)
    # the 4th train_step call (step index 3) was poisoned and skipped
    skipped = [r for r in reports if r.skipped]
    assert [r.step for r in skipped] == [3]
    assert skipped[0].reasons == (reason,)
    assert out["step"] == 6 and len(reports) == 6
    assert M.TRAIN_STEPS_SKIPPED.value == before + 1
    # final state == a clean run that never saw step 3's update
    ref_lora, ref_opt = _manual(step_fn, batch_fn, lora0, opt0,
                                [0, 1, 2, 4, 5])
    np.testing.assert_array_equal(_w(out["lora"]), _w(ref_lora))
    ev = [e for e in _events(tmp_path) if e["kind"] == "anomaly"]
    assert len(ev) == 1 and ev[0]["step"] == 3
    assert ev[0]["reasons"] == [reason]


@pytest.mark.core
def test_skip_keeps_opt_state_bit_identical(tmp_path):
    """The anomalous step's computed update is discarded whole: lora
    AND optimizer state after the skip are the pre-step buffers."""
    step_fn, batch_fn, lora0, opt0 = _toy()
    inj = TrainFaultInjector(seed=0).arm("nan_loss", times=1, after=2)
    sup = _sup(tmp_path, step_fn, lora0, opt0, faults=inj)
    sup.resume()
    out = sup.run(batch_fn, 3)  # steps 0, 1 applied; step 2 skipped
    ref_lora, ref_opt = _manual(step_fn, batch_fn, lora0, opt0, [0, 1])
    for got, want in zip(jax.tree.leaves(out["opt_state"]),
                         jax.tree.leaves(ref_opt)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(_w(out["lora"]), _w(ref_lora))


def test_spike_guard_waits_for_warmup(tmp_path):
    """A spike on the very first steps (no EMA baseline yet) must not
    trigger: warmup gates the spike guard, NaN guards stay armed."""
    step_fn, batch_fn, lora0, opt0 = _toy()
    inj = TrainFaultInjector(seed=0).arm("loss_spike", times=1, after=0)
    sup = _sup(tmp_path, step_fn, lora0, opt0, faults=inj, warmup_steps=3)
    sup.resume()
    reports = []
    sup.run(batch_fn, 4, on_step=reports.append)
    assert not any(r.skipped for r in reports)


# ---------------------------------------------------------------------------
# rollback
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_rollback_restores_last_good_checkpoint(tmp_path):
    step_fn, batch_fn, lora0, opt0 = _toy()
    inj = TrainFaultInjector(seed=0).arm("nan_loss", times=2, after=2)
    before = M.TRAIN_ROLLBACKS.value
    sup = _sup(tmp_path, step_fn, lora0, opt0, faults=inj,
               save_every=2, max_consecutive_anomalies=2)
    sup.resume()
    out = sup.run(batch_fn, 6)
    # steps 2 and 3 were anomalous -> rollback to the step-2 checkpoint,
    # then a clean replay of 2..5: the injected run converges to the
    # clean run's exact final state
    assert M.TRAIN_ROLLBACKS.value == before + 1
    ref_lora, _ = _manual(step_fn, batch_fn, lora0, opt0, range(6))
    np.testing.assert_array_equal(_w(out["lora"]), _w(ref_lora))
    ev = [e for e in _events(tmp_path) if e["kind"] == "rollback"]
    assert len(ev) == 1 and ev[0]["restored_step"] == 2


def test_rollback_loop_aborts_with_diagnostic(tmp_path):
    step_fn, batch_fn, lora0, opt0 = _toy()
    inj = TrainFaultInjector(seed=0).arm("nan_loss", times=-1)
    sup = _sup(tmp_path, step_fn, lora0, opt0, faults=inj,
               max_consecutive_anomalies=2, max_rollbacks=1)
    sup.resume()
    with pytest.raises(SupervisorAbort, match="rollback_loop") as ei:
        sup.run(batch_fn, 50)
    assert ei.value.kind == "rollback_loop"
    assert any(e["kind"] == "abort" for e in _events(tmp_path))


# ---------------------------------------------------------------------------
# preemption: injected signal, emergency checkpoint, bit-exact resume
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_preempt_signal_emergency_checkpoint_then_bitexact_resume(
        tmp_path):
    step_fn, batch_fn, lora0, opt0 = _toy()
    inj = TrainFaultInjector(seed=0).arm("preempt_signal", times=1,
                                         after=3)
    before = M.TRAIN_EMERGENCY_CHECKPOINTS.value
    sup = _sup(tmp_path, step_fn, lora0, opt0, faults=inj)
    sup.resume()
    with pytest.raises(SystemExit) as ei:
        sup.run(batch_fn, 6)
    assert ei.value.code == EXIT_PREEMPTED == 43
    assert M.TRAIN_EMERGENCY_CHECKPOINTS.value == before + 1
    # boundary semantics: steps 0..2 applied, emergency save at step 3
    assert list_train_checkpoints(str(tmp_path))[0].endswith(
        "ckpt-00000003.npz")
    assert any(e["kind"] == "preempt" for e in _events(tmp_path))

    # "restarted pod": a fresh supervisor over the same dir resumes and
    # finishes; final state equals an uninterrupted clean run, bit-exact
    step_fn2, batch_fn2, lora0b, opt0b = _toy()
    sup2 = _sup(tmp_path, step_fn2, lora0b, opt0b)
    assert sup2.resume() == 3
    out = sup2.run(batch_fn2, 6)
    ref_lora, _ = _manual(step_fn2, batch_fn2, lora0b, opt0b, range(6))
    np.testing.assert_array_equal(_w(out["lora"]), _w(ref_lora))


def test_sigterm_subprocess_emergency_exit_then_resume(tmp_path):
    """The REAL signal path: SIGTERM mid-run -> exit 43 with an
    emergency checkpoint; a rerun resumes at the interrupted step."""
    script = textwrap.dedent("""
        import sys, time
        import jax, jax.numpy as jnp, optax
        from bigdl_tpu.train.supervisor import (
            SupervisorConfig, TrainSupervisor)
        opt = optax.sgd(0.2)
        lora0 = {"layers": {"w": jnp.zeros((4,), jnp.float32)},
                 "scale": jnp.asarray(1.0, jnp.float32)}
        def step_fn(lora, opt_state, target):
            def loss_fn(layers):
                return jnp.sum((layers["w"] - target) ** 2)
            loss, g = jax.value_and_grad(loss_fn)(lora["layers"])
            up, opt_state = opt.update(g, opt_state, lora["layers"])
            layers = optax.apply_updates(lora["layers"], up)
            return ({"layers": layers, "scale": lora["scale"]},
                    opt_state, loss)
        def batch_fn(step):
            time.sleep(0.15)
            return (jnp.full((4,), float(step % 3 + 1), jnp.float32),)
        sup = TrainSupervisor(
            step_fn, ckpt_dir=sys.argv[1], lora=lora0,
            opt_state=opt.init(lora0["layers"]),
            rng=jax.random.PRNGKey(0),
            config=SupervisorConfig(save_every=100),
        )
        sup.install_signal_handlers()
        start = sup.resume()
        print(f"started at {start}", flush=True)
        def on_step(r):
            print(f"did step {r.step}", flush=True)
        sup.run(batch_fn, int(sys.argv[2]), on_step=on_step)
        print("completed", flush=True)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    proc = subprocess.Popen(
        [sys.executable, "-c", script, str(tmp_path), "1000"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env,
    )
    # wait until the loop demonstrably runs, then preempt it
    t0 = time.time()
    line = ""
    while time.time() - t0 < 120:
        line = proc.stdout.readline()
        if line.startswith("did step 2"):
            break
    assert line, "child never reached step 2"
    proc.send_signal(signal.SIGTERM)
    out, err = proc.communicate(timeout=120)
    assert proc.returncode == 43, (out, err)
    assert list_train_checkpoints(str(tmp_path)), "no emergency ckpt"

    # restart: must resume past step 0 and run to completion
    r2 = subprocess.run(
        [sys.executable, "-c", script, str(tmp_path), "8"],
        capture_output=True, text=True, timeout=180, env=env,
    )
    assert r2.returncode == 0, (r2.stdout, r2.stderr)
    started = int(r2.stdout.splitlines()[0].split()[-1])
    assert started >= 3
    assert "completed" in r2.stdout


# ---------------------------------------------------------------------------
# watchdog + rank drop: structured aborts, never a silent hang
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_watchdog_fires_on_hang_step(tmp_path):
    step_fn, batch_fn, lora0, opt0 = _toy()
    inj = TrainFaultInjector(seed=0).arm("hang_step", times=1, after=1,
                                         seconds=0.9)
    fired = []
    before = M.TRAIN_WATCHDOG_ABORTS.value
    sup = _sup(tmp_path, step_fn, lora0, opt0, faults=inj,
               step_timeout_s=0.25)
    sup._on_watchdog_timeout = fired.append
    sup.resume()
    out = sup.run(batch_fn, 4)
    assert len(fired) == 1 and fired[0] > 0.25
    assert M.TRAIN_WATCHDOG_ABORTS.value == before + 1
    ev = [e for e in _events(tmp_path) if e["kind"] == "watchdog_abort"]
    assert len(ev) == 1 and ev[0]["exit_code"] == 42
    assert out["step"] == 4  # the test hook kept the process alive


@pytest.mark.core
def test_rank_drop_aborts_with_structured_diagnostic(tmp_path):
    step_fn, batch_fn, lora0, opt0 = _toy()
    inj = TrainFaultInjector(seed=0).arm("rank_drop", times=1, after=1)
    sup = _sup(tmp_path, step_fn, lora0, opt0, faults=inj,
               heartbeat_every=1)
    sup.resume()
    with pytest.raises(SupervisorAbort, match="rank") as ei:
        sup.run(batch_fn, 10)
    assert ei.value.kind == "rank_drop"
    ev = [e for e in _events(tmp_path) if e["kind"] == "rank_drop"]
    assert len(ev) == 1 and ev[0]["missing"] == [0]  # 1-proc victim


# ---------------------------------------------------------------------------
# health layer
# ---------------------------------------------------------------------------

def test_anomaly_consensus_reduces_across_ranks():
    def gather4(row):
        # simulate 4 hosts: rank 2 saw the anomaly, we did not
        return np.stack([row * 0, row * 0, row * 0 + 1, row * 0])

    assert anomaly_consensus(False, allgather=gather4) is True
    assert anomaly_consensus(False) is False  # single process: identity
    assert anomaly_consensus(True) is True
    # vector form: element-wise OR in one collective
    from bigdl_tpu.parallel.health import consensus_any

    def gather2(row):
        peer = np.array([0.0, 1.0])  # the peer is preempting, no anomaly
        return np.stack([row, peer])

    assert consensus_any([False, False], allgather=gather2) == [False, True]
    assert consensus_any([True, False]) == [True, False]


def test_peer_preemption_propagates_through_consensus(tmp_path,
                                                      monkeypatch):
    """Another rank's SIGTERM (consensus preempt=True with the local
    flag unset) must make THIS rank exit 43 at the next boundary too —
    one evicted host never strands its peers in a wedged collective."""
    import bigdl_tpu.parallel.health as health

    step_fn, batch_fn, lora0, opt0 = _toy()
    calls = []

    def fake_consensus(flags, allgather=None):
        calls.append(list(flags))
        # after two clean steps, a peer reports preemption
        return [flags[0], True] if len(calls) >= 3 else [flags[0], False]

    monkeypatch.setattr(health, "consensus_any", fake_consensus)
    sup = _sup(tmp_path, step_fn, lora0, opt0)
    sup.resume()
    with pytest.raises(SystemExit) as ei:
        sup.run(batch_fn, 10)
    assert ei.value.code == 43
    assert all(f[1] is False for f in calls)  # local flag never set
    assert list_train_checkpoints(str(tmp_path))[0].endswith(
        "ckpt-00000003.npz")  # boundary after the third step


def test_health_monitor_detects_missing_and_stale_ranks():
    # all three ranks present and fresh
    now = time.time()
    rows = {0: np.array([0.0, 7, now]), 1: np.array([1.0, 7, now]),
            2: np.array([2.0, 7, now])}
    mon = HealthMonitor(num_processes=3, process_index=0,
                        allgather=lambda r: np.stack(list(rows.values())))
    assert [s.rank for s in mon.check(7)] == [0, 1, 2]
    # rank 1 gone
    del rows[1]
    with pytest.raises(RankDropError, match=r"\[1\] missing"):
        mon.check(8)
    # rank 2 present but stuck 5 steps back
    rows[1] = np.array([1.0, 9, time.time()])
    rows[2] = np.array([2.0, 4, time.time()])
    mon2 = HealthMonitor(num_processes=3, process_index=0,
                         max_step_lag=3,
                         allgather=lambda r: np.stack(list(rows.values())))
    with pytest.raises(RankDropError, match="stale"):
        mon2.check(9)


def test_init_multihost_retry_backoff():
    calls = []

    def flaky(**kw):
        calls.append(kw)
        if len(calls) < 3:
            raise RuntimeError("coordinator not up yet")

    n = init_multihost_with_retry(attempts=5, backoff_s=0.01,
                                  init_fn=flaky)
    assert n == 3 and len(calls) == 3
    # exhausted attempts re-raise the real error
    with pytest.raises(RuntimeError, match="still down"):
        init_multihost_with_retry(
            attempts=2, backoff_s=0.01,
            init_fn=lambda **kw: (_ for _ in ()).throw(
                RuntimeError("still down")),
        )
    # config errors are NOT retried
    bad_calls = []

    def bad_config(**kw):
        bad_calls.append(1)
        raise ValueError("partial coordinator config")

    with pytest.raises(ValueError):
        init_multihost_with_retry(attempts=5, backoff_s=0.01,
                                  init_fn=bad_config)
    assert len(bad_calls) == 1


# ---------------------------------------------------------------------------
# resume-scan integrity accounting (ISSUE 10 satellite fix)
# ---------------------------------------------------------------------------

def _corrupt_member_payload(path, member="leaf_00000.npy"):
    with zipfile.ZipFile(path) as zf:
        info = zf.getinfo(member)
    # payload starts after the 30-byte local header + filename (+extra,
    # empty for writestr members)
    off = info.header_offset + 30 + len(member) + 16
    with open(path, "r+b") as f:
        f.seek(off)
        f.write(b"\xde\xad\xbe\xef")


def _toy_state():
    lora = {"layers": {"w": jnp.arange(4, dtype=jnp.float32)},
            "scale": jnp.asarray(1.0, jnp.float32)}
    opt = optax.sgd(0.1).init(lora["layers"])
    return lora, opt


@pytest.mark.core
def test_skip_corrupt_resume_bumps_verify_failures(tmp_path):
    from bigdl_tpu.utils.durability import VERIFY_FAILURES

    lora, opt = _toy_state()
    rng = jax.random.PRNGKey(0)
    save_train_state_rotating(str(tmp_path), step=1, lora=lora,
                              opt_state=opt, rng=rng)
    newest = save_train_state_rotating(str(tmp_path), step=2, lora=lora,
                                       opt_state=opt, rng=rng)
    _corrupt_member_payload(newest)
    before = VERIFY_FAILURES.value
    with pytest.warns(UserWarning, match="skipping corrupt"):
        state = load_latest_train_state(
            str(tmp_path), like_lora=lora, like_opt_state=opt,
        )
    # the scan fell back to the older good checkpoint AND the process-
    # wide metric counted the corruption (not only direct verify= loads)
    assert state is not None and state["step"] == 1
    assert VERIFY_FAILURES.value > before


@pytest.mark.core
def test_rotted_format_version_is_skipped_not_fatal(tmp_path):
    """A parsed meta with a rotted format_version used to raise a bare
    ValueError that killed the whole resume scan; it must be a counted,
    skippable IntegrityError like any other corruption."""
    from bigdl_tpu.utils.durability import VERIFY_FAILURES

    lora, opt = _toy_state()
    rng = jax.random.PRNGKey(0)
    save_train_state_rotating(str(tmp_path), step=1, lora=lora,
                              opt_state=opt, rng=rng)
    newest = save_train_state_rotating(str(tmp_path), step=2, lora=lora,
                                       opt_state=opt, rng=rng)
    # rewrite the meta member with a rotted format_version; every leaf
    # member keeps its exact bytes so only the version check can fire
    with zipfile.ZipFile(newest) as zf:
        members = {i.filename: zf.read(i) for i in zf.infolist()}
    meta = json.loads(str(np.load(newest, allow_pickle=False)["meta"]))
    meta["format_version"] = 99
    import io

    buf = io.BytesIO()
    np.lib.format.write_array(
        buf, np.asarray(json.dumps(meta)), allow_pickle=False)
    members["meta.npy"] = buf.getvalue()
    with zipfile.ZipFile(newest, "w", zipfile.ZIP_STORED) as zf:
        for name, data in members.items():
            zf.writestr(name, data)
    before = VERIFY_FAILURES.value
    with pytest.warns(UserWarning, match="format_version"):
        state = load_latest_train_state(
            str(tmp_path), like_lora=lora, like_opt_state=opt,
        )
    assert state is not None and state["step"] == 1
    assert VERIFY_FAILURES.value > before


# ---------------------------------------------------------------------------
# metrics exposition
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_metrics_render_training_series():
    text = M.Metrics().render()
    for name in ("bigdl_tpu_train_anomalies_total",
                 "bigdl_tpu_train_steps_skipped_total",
                 "bigdl_tpu_train_rollbacks_total",
                 "bigdl_tpu_train_emergency_checkpoints_total",
                 "bigdl_tpu_train_watchdog_aborts_total"):
        assert f"# TYPE {name} counter" in text and name + " " in text
    assert "bigdl_tpu_train_step_seconds_bucket" in text
    assert 'le="600.0"' in text  # training-scale buckets, not request's


# ---------------------------------------------------------------------------
# integration: the real QLoRA step on the dryrun multihost mesh
# ---------------------------------------------------------------------------

def test_supervised_qlora_on_dryrun_multihost_mesh(tmp_path):
    """The deploy wiring in miniature: sharded tiny-llama QLoRA step on
    a dp×tp mesh over the 8 virtual CPU devices, supervised, with a NaN
    injected mid-run — the run skips it and still resumes bit-exactly
    from its rotating checkpoint afterwards."""
    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import PRESETS
    from bigdl_tpu.parallel.multihost import host_aware_mesh
    from bigdl_tpu.parallel.sharding import (
        expand_specs_for_params, lora_specs, param_specs, shard_params,
    )
    from bigdl_tpu.train import init_lora, make_train_step

    cfg = PRESETS["tiny-llama"]
    mesh = host_aware_mesh(tp=2, axes=("dp", "pp", "sp", "tp"))
    params = llama.quantize_params(
        llama.init_params(cfg, jax.random.PRNGKey(0)), "sym_int4")
    params = shard_params(
        params, expand_specs_for_params(param_specs(cfg), params), mesh)
    lora = init_lora(cfg, jax.random.PRNGKey(1), rank=4)
    lora = shard_params(
        lora,
        expand_specs_for_params(lora_specs(cfg, tuple(lora["layers"])),
                                lora),
        mesh)
    optimizer = optax.adamw(1e-3)
    opt_state = optimizer.init(lora["layers"])
    step_j = jax.jit(make_train_step(cfg, llama.forward, optimizer,
                                     return_grad_norm=True))

    def supervised_step(lora_t, opt_t, tokens, mask):
        with jax.set_mesh(mesh):
            return step_j(params, lora_t, opt_t, tokens, mask)

    rng = np.random.default_rng(0)

    def batch_fn(step):
        toks = jnp.asarray(
            rng.integers(1, cfg.vocab_size, (4, 17)), jnp.int32)
        return toks, jnp.ones_like(toks, jnp.float32)

    inj = TrainFaultInjector(seed=0).arm("nan_loss", times=1, after=1)
    sup = TrainSupervisor(
        supervised_step, ckpt_dir=str(tmp_path), lora=lora,
        opt_state=opt_state, rng=jax.random.PRNGKey(42),
        config=SupervisorConfig(save_every=2, warmup_steps=2,
                                heartbeat_every=0),
        faults=inj,
    )
    sup.resume()
    reports = []
    out = sup.run(batch_fn, 3, on_step=reports.append)
    assert [r.skipped for r in reports] == [False, True, False]
    assert np.isfinite([r.loss for r in reports if not r.skipped]).all()
    assert out["step"] == 3

    # restart resumes from the final rotating checkpoint bit-exactly
    lora2 = init_lora(cfg, jax.random.PRNGKey(1), rank=4)
    sup2 = TrainSupervisor(
        supervised_step, ckpt_dir=str(tmp_path), lora=lora2,
        opt_state=optimizer.init(lora2["layers"]),
        rng=jax.random.PRNGKey(42),
        config=SupervisorConfig(heartbeat_every=0),
    )
    assert sup2.resume() == 3
    for t, t2 in zip(jax.tree.leaves(out["lora"]),
                     jax.tree.leaves(sup2.lora)):
        np.testing.assert_array_equal(np.asarray(t), np.asarray(t2))

    # preemption on the mesh path: injected SIGTERM at the next step
    # boundary -> emergency checkpoint + exit 43 (same jitted step)
    inj2 = TrainFaultInjector(seed=0).arm("preempt_signal", times=1,
                                          after=1)
    sup3 = TrainSupervisor(
        supervised_step, ckpt_dir=str(tmp_path), lora=lora2,
        opt_state=optimizer.init(lora2["layers"]),
        rng=jax.random.PRNGKey(42),
        config=SupervisorConfig(heartbeat_every=0), faults=inj2,
    )
    sup3.resume()
    with pytest.raises(SystemExit) as ei:
        sup3.run(batch_fn, 6)
    assert ei.value.code == 43
    assert list_train_checkpoints(str(tmp_path))[0].endswith(
        "ckpt-00000004.npz")  # one step past the resume point
