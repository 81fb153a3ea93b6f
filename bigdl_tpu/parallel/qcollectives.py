"""Quantized ICI collectives: block-scaled int8 / fp8_e4m3 ring
all-reduce and all-gather with per-rank error feedback.

TP decode is latency-bound on the per-layer all-reduce (one per o-proj
and one per down-proj epilogue), and PP/multihost weight distribution is
bandwidth-bound on the all-gather. EQuARX (arxiv 2506.17615) shows a
block-scaled quantized all-reduce recovers most of that ICI bandwidth at
negligible quality cost, and arxiv 2301.12017 gives the composability
argument for stacking low-bit comms on top of already-quantized weights
— exactly this stack, where every TP epilogue sits downstream of a fused
dequant-GEMM.

Codec (docs/parallelism.md): the payload of every ring hop is the
partial sum flattened, zero-padded to a multiple of ``block_size``, and
encoded as per-block absmax-scaled int8 (d = absmax/127) or fp8_e4m3
(d = absmax/448) with float16 scales — the same per-block symmetric
format as `quant/numerics.py` (whose primitives this reuses), at a
comm-tuned block size (default 256: scale overhead 2/256 bytes/elem).

Algorithm — reduce-scatter ring + all-gather ring, both on
``jax.lax.ppermute`` with the neighbor permutation `ring.py` uses:

* reduce-scatter (n-1 hops): chunk ``c`` starts as rank ``c+1``'s local
  slice and travels the ring accumulating each stop's local slice, so
  after n-1 hops rank ``r`` owns the fully-reduced chunk ``r``. Every
  hop's payload is quantized; **error feedback** keeps the residual of
  hop *k*'s quantization on the sender and adds it back before
  quantizing hop *k+1*, so codec error does not compound around the
  ring (the property `tests/test_qcollectives.py` checks).
* all-gather (n-1 hops): each owner quantizes its reduced chunk ONCE
  and the encoded payload is forwarded unchanged; the owner itself uses
  the decoded version of its own chunk, so all ranks reconstruct
  bit-identical output.

``qtype="none"`` bypasses all of this and calls ``jax.lax.psum`` /
``jax.lax.all_gather`` — bit-identical to the unquantized path.

Everything here is device-local (runs inside `jax.shard_map`, the
kernels' Manual-axis context) and CPU-testable on virtual devices.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

# the per-block symmetric codec primitives (quant/numerics.py): blocked
# views, safe reciprocal, fp8 format ranges/dtypes
from bigdl_tpu.quant.numerics import _FP8_DTYPE, _FP8_MAX, _safe_inv

COMM_QTYPES = ("none", "int8", "fp8_e4m3")

#: comm-tuned block: 2 scale bytes per 256 payload elems (~0.8% overhead)
DEFAULT_BLOCK = 256

#: declared exactness tolerance per comm qtype: max abs error of the
#: quantized all-reduce relative to max|fp32 result|, on any dryrun
#: mesh / ring size (error feedback keeps it hop-count independent).
TOLERANCE = {"int8": 2e-2, "fp8_e4m3": 8e-2}


def resolve_comm_qtype(name: Optional[str]) -> str:
    qt = "none" if name is None else str(name)
    if qt not in COMM_QTYPES:
        raise ValueError(
            f"unknown comm_qtype {name!r}; expected one of {COMM_QTYPES}"
        )
    return qt


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """The `comm_qtype` knob as a hashable (jit-static) bundle: which
    mesh axis the TP epilogues reduce over, the payload format, and the
    declared tolerance the parity tests/gates hold the codec to."""

    mesh: Mesh
    axis_name: str = "tp"
    qtype: str = "none"
    block_size: int = DEFAULT_BLOCK
    #: None = the format's declared default (`TOLERANCE`)
    tolerance: Optional[float] = None
    error_feedback: bool = True

    def __post_init__(self):
        resolve_comm_qtype(self.qtype)

    @property
    def axis_size(self) -> int:
        return int(self.mesh.shape.get(self.axis_name, 1))

    @property
    def enabled(self) -> bool:
        """Quantized routing only engages with a real ring; "none" (or
        a 1-wide axis) keeps the model on today's implicit-psum path,
        bit-identical."""
        return self.qtype != "none" and self.axis_size > 1

    def tol(self) -> float:
        if self.tolerance is not None:
            return float(self.tolerance)
        return TOLERANCE[self.qtype]


# ---------------------------------------------------------------------------
# codec: per-block absmax scales over a flat padded payload
# ---------------------------------------------------------------------------


def _encode(x: jax.Array, qtype: str, block_size: int):
    """Block-quantize a flat fp32 payload (length % block_size == 0).

    Returns (data, scales): int8 or fp8_e4m3 data of x's shape plus one
    float16 absmax scale per block — `quant/numerics.py`'s symmetric
    per-block format at a comm-tuned block size."""
    xb = x.reshape(-1, block_size)
    absmax = jnp.max(jnp.abs(xb), axis=-1)
    if qtype == "int8":
        d = absmax / 127.0
        data = jnp.clip(
            jnp.round(xb * _safe_inv(d)[:, None]), -127, 127
        ).astype(jnp.int8)
    elif qtype == "fp8_e4m3":
        d = absmax / _FP8_MAX["fp8_e4m3"]
        data = (xb * _safe_inv(d)[:, None]).astype(_FP8_DTYPE["fp8_e4m3"])
    else:
        raise ValueError(f"not a quantized comm format: {qtype!r}")
    return data.reshape(x.shape), d.astype(jnp.float16)


def _decode(data: jax.Array, scales: jax.Array, block_size: int) -> jax.Array:
    xb = data.astype(jnp.float32).reshape(-1, block_size)
    out = xb * scales.astype(jnp.float32)[:, None]
    return out.reshape(data.shape)


def _flatten_pad(x: jax.Array, multiple: int):
    """Flatten to fp32 and zero-pad to a length multiple (ragged last
    block: numerics._blocked refuses ragged dims, comms must not)."""
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    pad = (-n) % multiple
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    return flat, n


def encode_array(x: jax.Array, qtype: str, block_size: int = DEFAULT_BLOCK):
    """Codec over an arbitrary-shape array (ring-attention k/v payloads,
    weight shards): flatten, pad, block-quantize once."""
    flat, _ = _flatten_pad(x, block_size)
    return _encode(flat, qtype, block_size)


def decode_array(data: jax.Array, scales: jax.Array, shape, dtype,
                 block_size: int = DEFAULT_BLOCK) -> jax.Array:
    n = 1
    for s in shape:
        n *= s
    flat = _decode(data, scales, block_size)[:n]
    return flat.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# device-local collectives (call inside jax.shard_map)
# ---------------------------------------------------------------------------


def quantized_reduce_scatter(x: jax.Array, axis_name: str = "tp",
                             qtype: str = "int8",
                             axis_size: Optional[int] = None,
                             block_size: int = DEFAULT_BLOCK,
                             error_feedback: bool = True) -> jax.Array:
    """The reduce-scatter half of the ring: rank ``r`` returns the
    fully-reduced chunk ``r`` of `x` flattened and zero-padded to
    ``n * ceil(size / (n*block))`` — fp32, [padded_size / n].

    At hop h (1..n-1) rank r forwards the quantized partial for chunk
    (r-h) mod n and receives + accumulates chunk (r-h-1) mod n. With
    `error_feedback` the residual of rank r's hop-h encode rides into
    its hop-h+1 payload, telescoping the injected error around the ring
    so the AGGREGATE codec error stays at ~n dropped residuals instead
    of the n*(n-1) quantization events of the feedback-free ring — the
    sense in which error "does not compound with hop count"
    (tests/test_qcollectives.py measures exactly this)."""
    qt = resolve_comm_qtype(qtype)
    n = int(axis_size if axis_size is not None
            else jax.lax.psum(1, axis_name))
    me = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]

    flat, _ = _flatten_pad(x, n * block_size)
    chunks = flat.reshape(n, flat.shape[0] // n)
    if qt == "none":
        red = jax.lax.psum(chunks, axis_name)
        return jax.lax.dynamic_index_in_dim(red, me, 0, keepdims=False)

    def rs_step(carry, k):
        partial, err = carry
        v = partial + err if error_feedback else partial
        data, scales = _encode(v, qt, block_size)
        if error_feedback:
            err = v - _decode(data, scales, block_size)
        data = jax.lax.ppermute(data, axis_name, perm)
        scales = jax.lax.ppermute(scales, axis_name, perm)
        recv = _decode(data, scales, block_size)
        local = jax.lax.dynamic_index_in_dim(
            chunks, (me - k - 2) % n, axis=0, keepdims=False
        )
        return (recv + local, err), None

    p0 = jax.lax.dynamic_index_in_dim(
        chunks, (me - 1) % n, axis=0, keepdims=False
    )
    (own, _), _ = jax.lax.scan(
        rs_step, (p0, jnp.zeros_like(p0)), jnp.arange(n - 1)
    )
    return own


def quantized_psum(x: jax.Array, axis_name: str = "tp",
                   qtype: str = "int8", axis_size: Optional[int] = None,
                   block_size: int = DEFAULT_BLOCK,
                   error_feedback: bool = True) -> jax.Array:
    """All-reduce `x` over `axis_name` through the quantized ring.

    Reduce-scatter with per-rank error feedback, then a single-encode
    all-gather (module docstring has the hop math). ``qtype="none"``
    is exactly ``jax.lax.psum``. `error_feedback=False` exists for the
    property test that shows feedback is what keeps the ring's
    aggregate error hop-count independent — production paths leave it
    on."""
    qt = resolve_comm_qtype(qtype)
    if qt == "none":
        return jax.lax.psum(x, axis_name)
    n = int(axis_size if axis_size is not None
            else jax.lax.psum(1, axis_name))
    if n == 1:
        return x
    me = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]

    flat, nelem = _flatten_pad(x, n * block_size)
    chunks = flat.reshape(n, flat.shape[0] // n)
    own = quantized_reduce_scatter(
        x, axis_name, qtype=qt, axis_size=n, block_size=block_size,
        error_feedback=error_feedback,
    )

    # all-gather: encode the owned chunk ONCE and forward the payload;
    # every rank (owner included) uses the decoded version, so outputs
    # are bit-identical across the ring.
    data, scales = _encode(own, qt, block_size)
    out = jnp.zeros_like(chunks)
    out = out.at[me].set(_decode(data, scales, block_size))

    def ag_step(carry, g):
        acc, d, s = carry
        d = jax.lax.ppermute(d, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        # after g+1 forwards we hold the chunk owned by rank me-g-1
        acc = acc.at[(me - g - 1) % n].set(_decode(d, s, block_size))
        return (acc, d, s), None

    (out, _, _), _ = jax.lax.scan(
        ag_step, (out, data, scales), jnp.arange(n - 1)
    )
    return out.reshape(-1)[:nelem].reshape(x.shape).astype(x.dtype)


def quantized_all_gather(x: jax.Array, axis_name: str = "tp",
                         qtype: str = "int8",
                         axis_size: Optional[int] = None,
                         block_size: int = DEFAULT_BLOCK,
                         tiled: bool = False) -> jax.Array:
    """All-gather `x` over `axis_name` with block-quantized payloads
    (PP/multihost weight and KV-page distribution). Each shard encodes
    ONCE; payloads ride the ring n-1 hops unchanged, so every rank
    decodes identical bytes. ``qtype="none"`` is ``jax.lax.all_gather``."""
    qt = resolve_comm_qtype(qtype)
    if qt == "none":
        return jax.lax.all_gather(x, axis_name, tiled=tiled)
    n = int(axis_size if axis_size is not None
            else jax.lax.psum(1, axis_name))
    me = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]

    flat, _ = _flatten_pad(x, block_size)
    data, scales = _encode(flat, qt, block_size)

    def as_x(d, s):
        return decode_array(d, s, x.shape, x.dtype, block_size)

    out = jnp.zeros((n,) + tuple(x.shape), x.dtype)
    out = out.at[me].set(as_x(data, scales))

    def step(carry, g):
        acc, d, s = carry
        d = jax.lax.ppermute(d, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        acc = acc.at[(me - g - 1) % n].set(as_x(d, s))
        return (acc, d, s), None

    (out, _, _), _ = jax.lax.scan(
        step, (out, data, scales), jnp.arange(n - 1)
    )
    if tiled:
        out = out.reshape((n * x.shape[0],) + tuple(x.shape[1:]))
    return out


# ---------------------------------------------------------------------------
# whole-array wrappers (parity tests, dryrun harness)
# ---------------------------------------------------------------------------


def mesh_all_reduce(xs: jax.Array, mesh: Mesh, axis_name: str = "tp",
                    qtype: str = "int8",
                    block_size: int = DEFAULT_BLOCK,
                    error_feedback: bool = True) -> jax.Array:
    """Reduce stacked per-rank partials ``xs[i]`` (leading axis =
    ``mesh.shape[axis_name]``) through the quantized ring; returns the
    same stacked shape with every row holding the reduced result — the
    parity-test harness for `quantized_psum` on dp×sp×tp meshes."""
    n = int(mesh.shape[axis_name])
    if xs.shape[0] != n:
        raise ValueError(
            f"xs leading axis {xs.shape[0]} != mesh {axis_name}={n}"
        )
    spec = P(axis_name, *([None] * (xs.ndim - 1)))

    def body(local):
        red = quantized_psum(
            local[0], axis_name, qtype=qtype, axis_size=n,
            block_size=block_size, error_feedback=error_feedback,
        )
        return red[None]

    f = jax.shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                   check_vma=False)
    return f(xs)


def mesh_reduce_scatter(xs: jax.Array, mesh: Mesh, axis_name: str = "tp",
                        qtype: str = "int8",
                        block_size: int = DEFAULT_BLOCK,
                        error_feedback: bool = True) -> jax.Array:
    """Reduce stacked per-rank partials ``xs[i]`` and return the
    reassembled flat reduced vector (chunk r from rank r, concatenated;
    zero-padding included) — the error-feedback property test's view of
    the reduce-scatter half in isolation."""
    n = int(mesh.shape[axis_name])
    if xs.shape[0] != n:
        raise ValueError(
            f"xs leading axis {xs.shape[0]} != mesh {axis_name}={n}"
        )
    spec = P(axis_name, *([None] * (xs.ndim - 1)))

    def body(local):
        own = quantized_reduce_scatter(
            local[0], axis_name, qtype=qtype, axis_size=n,
            block_size=block_size, error_feedback=error_feedback,
        )
        return own[None]

    f = jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                   out_specs=P(axis_name, None), check_vma=False)
    return f(xs).reshape(-1)


def mesh_all_gather(x: jax.Array, mesh: Mesh, axis_name: str = "tp",
                    qtype: str = "none",
                    block_size: int = DEFAULT_BLOCK) -> jax.Array:
    """Replicate an axis-0-sharded array (a weight shard table, a KV
    page pool) via the quantized ring all-gather: every device ends up
    holding the full array, paying quantized instead of fp32 bytes on
    the wire."""
    n = int(mesh.shape[axis_name])
    if x.shape[0] % n:
        raise ValueError(
            f"axis 0 ({x.shape[0]}) not divisible by {axis_name}={n}"
        )
    spec = P(axis_name, *([None] * (x.ndim - 1)))

    def body(local):
        return quantized_all_gather(
            local, axis_name, qtype=qtype, axis_size=n,
            block_size=block_size, tiled=True,
        )

    f = jax.shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=P(),
                   check_vma=False)
    return f(x)
