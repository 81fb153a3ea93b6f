"""Multihost QLoRA finetune entrypoint — the job each pod of the
TPU JobSet runs (deploy/k8s/qlora-multihost-v5e-16.yaml).

TPU-native replacement for the reference's MPI launcher+worker pair
(/root/reference/docker/llm/finetune/lora/cpu/kubernetes/templates/
ipex-llm-lora-finetuning-job.yaml:7-54 + the oneCCL/ssh bootstrap in its
entrypoint): every process runs THIS script unchanged; the only
distributed step is the coordinator join (retried with backoff —
parallel/health.init_multihost_with_retry — because the process-0 pod
routinely comes up after its peers), after which the dp×tp train step
is a single jitted SPMD program — gradient psums over dp ride DCN once
per step, tp psums stay on ICI (parallel/multihost.host_aware_mesh).

Data: a .jsonl with {"tokens": [int, ...]} rows (pre-tokenized), or
{"text": ...} rows if a tokenizer can be loaded from the model dir.
Every host reads the SAME file and takes its dp-rank's strided rows —
no shared filesystem coordination beyond the read-only mounts.

Resilience (train/supervisor.py — the whole loop runs supervised):

- rotating checkpoints `ckpt-<step>.npz` every --save-every steps with
  keep-last-k retention, and **unconditional auto-resume**: a restarted
  pod adopts the newest loadable checkpoint (corrupt candidates are
  skipped, counted, and warned about) and continues bit-exactly. A
  legacy single-file `train_state.npz` from a pre-supervisor run is
  adopted once and migrated into the rotation.
- NaN/inf loss + grad-norm guards and an EMA loss-spike detector:
  anomalous steps are skipped with the optimizer state untouched (the
  skip verdict is cross-host reduced, so SPMD state can never fork);
  K consecutive anomalies roll back to the last good checkpoint.
- SIGTERM/SIGINT (k8s preemption) takes an emergency checkpoint at the
  next step boundary and exits 43; the restarted pod resumes.
- a hung step (wedged DCN collective) exits 42 with a diagnostic
  (BIGDL_TPU_WATCHDOG_S, set in the k8s job spec).

Exit codes: 0 done · 42 watchdog (hung step) · 43 preempted with
emergency checkpoint. The job spec's restartPolicy treats 42/43 as
restart-and-resume. `bigdl-tpu train-status <ckpt-dir>` shows the
rotation inventory and the supervisor's event log.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", required=True,
                   help="HF checkpoint dir / saved low-bit dir / preset name")
    p.add_argument("--data", required=True, help="train .jsonl")
    p.add_argument("--ckpt-dir", default="/ckpt")
    p.add_argument("--qtype", default="nf4")
    p.add_argument("--rank", type=int, default=8)
    p.add_argument("--batch-per-host", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--steps", type=int, default=1000)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel width (must divide one host's "
                        "chip count; dp spans the rest of the pod)")
    p.add_argument("--save-every", type=int, default=100)
    p.add_argument("--keep-last", type=int, default=3,
                   help="checkpoint rotation retention")
    p.add_argument("--spike-factor", type=float, default=10.0,
                   help="loss > factor x EMA counts as an anomaly")
    p.add_argument("--max-anomalies", type=int, default=3,
                   help="consecutive anomalous steps before rollback")
    return p.parse_args(argv)


def load_rows(path: str, seq_len: int, tokenizer=None):
    """Yield fixed-length token rows from a jsonl forever (epoch loop)."""
    while True:
        with open(path) as f:
            buf: list[int] = []
            for line in f:
                if not line.strip():
                    continue
                row = json.loads(line)
                if "tokens" in row:
                    ids = [int(t) for t in row["tokens"]]
                elif tokenizer is not None:
                    ids = list(tokenizer(row["text"])["input_ids"])
                else:
                    raise ValueError(
                        "rows carry 'text' but no tokenizer is available; "
                        "pre-tokenize to {'tokens': [...]} instead"
                    )
                buf.extend(ids)
                while len(buf) >= seq_len + 1:
                    yield buf[: seq_len + 1]
                    buf = buf[seq_len + 1:]


def main(argv=None) -> int:
    args = parse_args(argv)

    import jax

    from bigdl_tpu.parallel.health import init_multihost_with_retry
    from bigdl_tpu.parallel.multihost import host_aware_mesh

    # no-op on a single host; on a pod, joins the coordinator under
    # bounded backoff (the process-0 pod may still be scheduling)
    init_multihost_with_retry()

    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bigdl_tpu.models import llama
    from bigdl_tpu.models.config import PRESETS
    from bigdl_tpu.parallel.sharding import (
        expand_specs_for_params, lora_specs, param_specs, shard_params,
    )
    from bigdl_tpu.train import init_lora, make_train_step
    from bigdl_tpu.train.checkpoint import (
        list_train_checkpoints, load_train_state,
    )
    from bigdl_tpu.train.supervisor import (
        SupervisorConfig, TrainSupervisor,
    )
    from bigdl_tpu.train.watchdog import timeout_from_env

    pid, nproc = jax.process_index(), jax.process_count()
    n_dev = len(jax.devices())
    dp = n_dev // args.tp
    mesh = host_aware_mesh(tp=args.tp, dp=dp, axes=("dp", "pp", "sp", "tp"))
    if pid == 0:
        print(f"[qlora] {nproc} hosts, {n_dev} chips, mesh dp={dp} "
              f"tp={args.tp}", flush=True)

    tokenizer = None
    if args.model in PRESETS:
        config = PRESETS[args.model]
        params = llama.quantize_params(
            llama.init_params(config, jax.random.PRNGKey(0)), args.qtype
        )
    else:
        from bigdl_tpu.convert import load_hf_checkpoint

        config, params, tokenizer = load_hf_checkpoint(
            args.model, qtype=args.qtype
        )

    specs = expand_specs_for_params(param_specs(config), params)
    params = shard_params(params, specs, mesh)
    lora = init_lora(config, jax.random.PRNGKey(1), rank=args.rank)
    lora_sp = expand_specs_for_params(
        lora_specs(config, tuple(lora["layers"])), lora
    )
    lora = shard_params(lora, lora_sp, mesh)

    optimizer = optax.adamw(args.lr)
    opt_state = optimizer.init(lora["layers"])
    step_fn = make_train_step(config, llama.forward, optimizer,
                              return_grad_norm=True)
    # NO donation: the supervisor's anomaly-skip path keeps the previous
    # lora/opt_state alive for one step (adapter state is small — the
    # price of an untouched optimizer after a NaN)
    step_j = jax.jit(step_fn)

    def supervised_step(lora_t, opt_t, tokens, mask):
        with jax.set_mesh(mesh):
            return step_j(params, lora_t, opt_t, tokens, mask)

    # hung-step detection rides the supervisor's watchdog: a lost peer
    # blocks every other host inside a collective with no exception —
    # the per-step host loss fetch is the beat, and silence past
    # BIGDL_TPU_WATCHDOG_S becomes exit 42 + restart + auto-resume
    sup = TrainSupervisor(
        supervised_step,
        ckpt_dir=args.ckpt_dir,
        lora=lora, opt_state=opt_state, rng=jax.random.PRNGKey(42),
        config=SupervisorConfig(
            save_every=args.save_every or args.steps,
            keep_last=args.keep_last,
            spike_factor=args.spike_factor,
            max_consecutive_anomalies=args.max_anomalies,
            step_timeout_s=timeout_from_env(),
        ),
        is_chief=(pid == 0), process_index=pid,
    )
    sup.install_signal_handlers()

    # unconditional auto-resume: newest loadable rotated checkpoint, or
    # (once) a legacy pre-supervisor train_state.npz — seeded BEFORE
    # resume() so the baseline save migrates it into the rotation
    legacy = os.path.join(args.ckpt_dir, "train_state.npz")
    if not list_train_checkpoints(args.ckpt_dir) and os.path.exists(legacy):
        state = load_train_state(
            legacy, like_lora=lora, like_opt_state=opt_state,
        )
        sup.lora, sup.opt_state = state["lora"], state["opt_state"]
        sup.rng, sup.step = state["rng"], state["step"]
    start_step = sup.resume()
    if start_step and pid == 0:
        print(f"[qlora] resumed at step {start_step}", flush=True)

    # dp-rank-strided data: host p consumes rows [p*B, (p+1)*B) of each
    # global batch of nproc*B rows, then skips the other hosts' rows —
    # without the per-batch skip every host would train on every row
    # (nproc duplicate gradients per sample)
    B = args.batch_per_host
    if (B * nproc) % dp != 0:
        raise SystemExit(
            f"global batch {B}*{nproc} hosts = {B * nproc} rows does not "
            f"divide over the dp={dp} mesh axis; set --batch-per-host to "
            f"a multiple of {max(dp // nproc, 1)}"
        )
    rows = load_rows(args.data, args.seq_len, tokenizer)
    for _ in range(pid * B):  # stagger host offsets
        next(rows)

    data_sharding = NamedSharding(mesh, P("dp", None))

    def batch_fn(step):
        # a data STREAM (ignores `step`): a rollback replays the model
        # state exactly but continues on fresh batches, which is the
        # right call for epoch-looped jsonl data
        batch = [next(rows) for _ in range(B)]
        for _ in range((nproc - 1) * B):  # the other hosts' rows
            next(rows)
        batch = np.stack(batch).astype(np.int32)
        tokens = jax.make_array_from_process_local_data(
            data_sharding, batch,
            global_shape=(B * nproc, args.seq_len + 1),
        ) if nproc > 1 else jax.device_put(jnp.asarray(batch), data_sharding)
        mask = jnp.ones_like(tokens, jnp.float32)
        return tokens, mask

    t0 = time.time()

    def on_step(report):
        if pid == 0 and report.skipped:
            print(f"[qlora] step {report.step}: SKIPPED "
                  f"({','.join(report.reasons)}; loss {report.loss:.4g})",
                  flush=True)
        elif pid == 0 and (report.step % 10 == 0
                           or report.step == args.steps - 1):
            dt = time.time() - t0
            print(f"[qlora] step {report.step}: loss {report.loss:.4f} "
                  f"({dt:.1f}s)", flush=True)

    sup.run(batch_fn, args.steps, on_step=on_step)
    if pid == 0:
        print("[qlora] done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
