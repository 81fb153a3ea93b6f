"""Pallas TPU kernels — the framework's native kernel layer.

These are the TPU counterparts of the reference's prebuilt SYCL/C++ kernel
wheels (`bigdl-core-xe*` / `xe_linear` / `xe_addons`, SURVEY.md §2.1): real
on-chip kernels for the hot ops, not Python stand-ins. Unlike the
reference (which ships opaque binaries), the kernels are source in-tree
and compile through Mosaic for the local chip.

Dispatch policy (`use_pallas()`), one switch, `BIGDL_TPU_PALLAS`:
- unset: the kernels are used on TPU backends and compile through
  Mosaic; every other backend takes the XLA route;
- `interpret`: the kernels are used on any backend and run through the
  Pallas interpreter (how the CPU tests exercise the kernel logic).
  Nothing else ever interprets a kernel: a kernel called directly on a
  backend without Mosaic fails instead of quietly interpreting;
- `0`: force-disables (XLA route everywhere).

One rule besides the switch: under a mesh axis that XLA's SPMD
partitioner owns (size > 1, not Manual) the kernels are off. XLA cannot
split a Mosaic call, so a `pallas_call` on sharded operands in a plain
`jit` would gather every operand whole to every chip on every call.
Inside `shard_map` the axis is Manual, each device sees its own shard,
and the kernels are on (`ops/linear.row_parallel_linear`).
"""

from __future__ import annotations

import os
from typing import Optional

import jax


def _mode() -> str:
    return os.environ.get("BIGDL_TPU_PALLAS", "auto")


def why_not_pallas() -> Optional[str]:
    """None when the kernels are in use for what is being traced now,
    else the reason they are not (shown by `ops/routes`)."""
    mode = _mode()
    if mode == "0":
        return "BIGDL_TPU_PALLAS=0"
    if mode != "interpret" and jax.default_backend() != "tpu":
        return f"backend is {jax.default_backend()}, not tpu"
    mesh = jax.sharding.get_abstract_mesh()
    spmd = [n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if mesh.shape[n] > 1 and t != jax.sharding.AxisType.Manual]
    if spmd:
        return (f"mesh axes {spmd} are partitioned by XLA, which cannot "
                "split a Mosaic call")
    return None


def use_pallas() -> bool:
    return why_not_pallas() is None


def interpret_mode() -> bool:
    """Run kernels through the Pallas interpreter: only when
    `BIGDL_TPU_PALLAS=interpret` asks for it."""
    return _mode() == "interpret"


from bigdl_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402
from bigdl_tpu.ops.pallas.flash_backward import (  # noqa: E402
    flash_attention_trainable,
)
from bigdl_tpu.ops.pallas.paged_attention import (  # noqa: E402
    paged_block_attention, paged_decode_attention,
    paged_latent_decode_attention,
)
from bigdl_tpu.ops.pallas.qbackward import (  # noqa: E402
    dw_matmul, qmatmul_dx,
)
from bigdl_tpu.ops.pallas.qmatmul import qmatmul, qmatmul_lora  # noqa: E402

__all__ = ["use_pallas", "why_not_pallas", "interpret_mode", "flash_attention",
           "flash_attention_trainable",
           "paged_block_attention",
           "paged_decode_attention", "paged_latent_decode_attention", "qmatmul",
           "qmatmul_lora", "qmatmul_dx", "dw_matmul"]
