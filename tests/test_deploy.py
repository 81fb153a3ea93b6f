"""Deployment packaging tests (VERDICT r04 missing #2, third ask): the
Dockerfiles must COPY paths that exist, the GKE manifests must be valid
k8s objects requesting TPU resources, and the multihost QLoRA
entrypoint must run end to end (train + checkpoint + resume) on the
virtual CPU mesh."""

import json
import pathlib
import subprocess
import sys

import pytest
import yaml

REPO = pathlib.Path(__file__).resolve().parent.parent
DEPLOY = REPO / "deploy"


def test_dockerfiles_copy_real_paths():
    for name in ("Dockerfile.serve", "Dockerfile.finetune"):
        df = (DEPLOY / name).read_text()
        for line in df.splitlines():
            if line.startswith("COPY "):
                src = line.split()[1]
                assert (REPO / src).exists(), f"{name}: COPY {src} missing"
        assert "jax[tpu]" in df  # libtpu wheel is the TPU runtime
        assert "ENTRYPOINT" in df


@pytest.mark.parametrize("manifest", ["serve-v5e-8.yaml",
                                      "qlora-multihost-v5e-16.yaml"])
def test_k8s_manifests_parse_and_request_tpus(manifest):
    docs = list(yaml.safe_load_all((DEPLOY / "k8s" / manifest).read_text()))
    assert docs
    containers = []

    def walk(node):
        if isinstance(node, dict):
            containers.extend(node.get("containers") or [])
            for v in node.values():
                walk(v)
        elif isinstance(node, list):
            for v in node:
                walk(v)

    for d in docs:
        assert d.get("apiVersion") and d.get("kind"), manifest
        walk(d)
    tpu_requests = [
        c["resources"]["limits"]["google.com/tpu"]
        for c in containers if "resources" in c
    ]
    assert tpu_requests, f"{manifest}: no container requests google.com/tpu"
    # every TPU container pins a node selector for the slice type
    text = (DEPLOY / "k8s" / manifest).read_text()
    assert "cloud.google.com/gke-tpu-accelerator" in text
    assert "cloud.google.com/gke-tpu-topology" in text


def test_serve_manifest_probe_hits_real_route():
    """The readiness probe path must be a route the server actually
    serves (a typo'd probe bricks the Deployment in CrashLoop)."""
    text = (DEPLOY / "k8s" / "serve-v5e-8.yaml").read_text()
    probe = [ln.split("path:")[1].strip() for ln in text.splitlines()
             if "path:" in ln]
    server_src = (REPO / "bigdl_tpu" / "serving" / "api_server.py").read_text()
    for path in probe:
        assert f'"{path}"' in server_src, f"probe path {path} not served"


# 44 s through the CLI in child processes, no cell's program: tier-1's wall
# time is its limit (ROADMAP D13 step (1), PR 57)
@pytest.mark.slow
def test_multihost_qlora_runs_and_resumes(tmp_path):
    """The finetune entrypoint trains on the virtual CPU mesh, writes
    the atomic train state, and a rerun resumes from it (the JobSet's
    preemption story) — all through the real CLI surface."""
    data = tmp_path / "train.jsonl"
    rows = [{"tokens": list(range(1, 40))} for _ in range(8)]
    data.write_text("\n".join(json.dumps(r) for r in rows))
    ckpt = tmp_path / "ckpt"

    def run(steps):
        return subprocess.run(
            [sys.executable, str(DEPLOY / "multihost_qlora.py"),
             "--model", "tiny-llama", "--data", str(data),
             "--ckpt-dir", str(ckpt), "--qtype", "sym_int4",
             "--rank", "4", "--batch-per-host", "8", "--seq-len", "16",
             "--steps", str(steps), "--save-every", "2"],
            capture_output=True, text=True, timeout=600,
            env={"JAX_PLATFORMS": "cpu",
                 "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                 "PATH": "/usr/bin:/bin:/usr/local/bin",
                 "PYTHONPATH": str(REPO),
                 "HOME": "/tmp"},
        )

    r = run(2)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loss" in r.stdout
    # supervised loop (train/supervisor.py): rotating checkpoints +
    # structured event log instead of the old single train_state.npz
    assert sorted(p.name for p in ckpt.glob("ckpt-*.npz")) == [
        "ckpt-00000000.npz", "ckpt-00000002.npz",
    ]
    assert (ckpt / "supervisor_events.jsonl").exists()

    r2 = run(4)  # resumes at step 2, trains 2 more
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed at step 2" in r2.stdout
    assert (ckpt / "ckpt-00000004.npz").exists()
