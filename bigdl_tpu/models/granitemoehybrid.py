"""Granite 4.0-H (`granitemoehybrid`, e.g. ibm-granite/granite-4.0-h-small):
Mamba-2 layers with a few softmax-attention layers between them, every
layer followed by top-k routed experts and an always-on shared MLP.

HF's modeling_granitemoehybrid is the behavioural spec. With H the hidden
size and `rs` the residual multiplier:

    h = embed(tokens) * embedding_multiplier
    per layer:  h = h + rs * mixer(rmsnorm(h))          mixer by layer_types
                u = rmsnorm(h);  h = h + rs * (moe(u) + shared(u))
    logits = rmsnorm(h) @ embed^T / logits_scaling      (tied embeddings)

Mamba-2 mixer (one group of B and C): `[z | xBC | dt] = in_proj(u)`;
`xBC = silu(causal depthwise conv(xBC) + b)`; `[x | B | C] = xBC`;
`dt = softplus(dt + dt_bias)`; per head the recurrence of `kvhybrid.py`
with `A = -exp(A_log)`; `y = rmsnorm(y * silu(z)) * w` over the whole inner
width (the gate BEFORE the norm); `out_proj`. Attention mixer: GQA with NO
position encoding (`position_embedding_type` "nope": no rope call at all),
softmax scale `attention_multiplier`, causal. Router: top-k of the LOGITS,
gates = softmax over the chosen logits in float32. Expert and shared MLP:
`[a | b] = W_in u`, `W_out (silu(a) * b)`.

Layout. `forward` walks `layer_types` as RUNS of layers of one kind: a scan
over each run of Mamba layers, the attention layers between them one by one.
`params["runs"]["00"]`, `["01"]`, ... stack each run's layers (a layer's
norms, router, experts and shared MLP ride with its mixer; a dict and not a
list: `save_low_bit` walks dicts), so that a scan takes a whole stack and
nothing is sliced out of a larger one first (a run's slice is a copy: 0.6 GB
of scales a decode step at granite-4.0-h-small's sizes). Packed codes reach
their kernels by the layer's index in its run, out of the scans' slices
(`linear(layer=)`, `_moe_dispatch(layer=)`), as in `llama.forward`; the
cache is indexed by the layer's index among its kind. The per-head decay rate is
kept as `a = exp(A_log)` (A = -a), what an inference engine computes once at
load, in float16: 11 bits on a number that a checkpoint draws from [1, 16],
beside 4-bit weights. (float16 is also the one dtype that
`bench/weights.py` draws small and positive; bench/configs says what that
does to the state's memory.) `dt_bias`, `D`, the convolution and the norms
stay float32 / the init dtype.

The cache is `kvhybrid.HybridCache`: pages for the attention layers and a
state row for the Mamba layers in one slot. `InferenceEngine(paged=True)`
gets it from `init_paged_cache` (through its kind,
`kvhybrid.CACHE_KIND`); `TpuModel.generate` gets one from `init_cache` with every row's
pages laid out in order.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import kvhybrid, kvpaged
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.obs.scopes import scope
from bigdl_tpu.ops import linear, rms_norm
from bigdl_tpu.ops.linear import stacks_in

Params = dict[str, Any]

# the per-layer weights that go through `linear`, by kind of run
_MIXER_STACKS = {"mamba": ("w_in", "w_out"),
                 "attention": ("wq", "wk", "wv", "wo")}
_SHARED_STACKS = ("w_gate_s", "w_up_s", "w_down_s")
_QUANT_TARGETS = (_MIXER_STACKS["mamba"] + _MIXER_STACKS["attention"]
                  + llama._EXPERT_STACKS + _SHARED_STACKS)
GENERATE_PAGE = 64  # tokens a page of `init_cache`'s pool


def dims(config: ModelConfig):
    """(heads, head size, state size, inner width, conv channels)."""
    H, P, N = config.mamba_n_heads, config.mamba_d_head, config.mamba_d_state
    inner = H * P
    return H, P, N, inner, inner + 2 * config.mamba_n_groups * N


def layer_runs(config: ModelConfig) -> list[tuple[str, int, int]]:
    """`layer_types` as runs: (kind, index of the run's first layer AMONG
    ITS KIND, length)."""
    runs, seen = [], {"mamba": 0, "attention": 0}
    for kind in config.layer_types:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen[kind], 1])
        seen[kind] += 1
    return [tuple(r) for r in runs]


def n_layers(config: ModelConfig, kind: str) -> int:
    return sum(k == kind for k in config.layer_types)


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16,
                scale: float = 0.02) -> Params:
    """Random init (tests/benchmarks run without checkpoints). The step
    sizes and decay rates are drawn as Mamba-2 initialises them: dt
    log-uniform in [0.001, 0.1], a uniform in [1, 16]."""
    H, P, N, inner, C = dims(config)
    hid, V = config.hidden_size, config.vocab_size
    E, I = config.num_experts, config.moe_intermediate_size
    S, K = config.shared_intermediate_size, config.mamba_d_conv
    keys = iter(jax.random.split(key, 16 * (len(layer_runs(config)) + 1)))

    def w(shape, std=scale, dt=dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dt)

    def block(n):  # what every layer has beside its mixer
        out = {"attn_norm": jnp.ones((n, hid), dtype),
               "mlp_norm": jnp.ones((n, hid), dtype)}
        if E:
            out.update(router=w((n, E, hid)), w_gate_e=w((n, E, I, hid)),
                       w_up_e=w((n, E, I, hid)), w_down_e=w((n, E, hid, I)))
        if S:
            out.update(w_gate_s=w((n, S, hid)), w_up_s=w((n, S, hid)),
                       w_down_s=w((n, hid, S)))
        return out

    def mamba(n):
        dt0 = jnp.exp(jax.random.uniform(
            next(keys), (n, H), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        return dict(
            block(n),
            w_in=w((n, inner + C + H, hid)), w_out=w((n, hid, inner)),
            conv_w=w((n, K, C), std=K ** -0.5, dt=jnp.float32),
            conv_b=jnp.zeros((n, C), jnp.float32),
            dt_bias=dt0 + jnp.log(-jnp.expm1(-dt0)),  # softplus^-1(dt0)
            a=jax.random.uniform(next(keys), (n, H), jnp.float32, 1.0,
                                 16.0).astype(jnp.float16),
            D=jnp.ones((n, H), jnp.float32),
            mixer_norm=jnp.ones((n, inner), dtype))

    def attention(n):
        QD, KD = config.q_dim, config.kv_dim
        return dict(block(n), wq=w((n, QD, hid)), wk=w((n, KD, hid)),
                    wv=w((n, KD, hid)), wo=w((n, hid, QD)))

    params: Params = {
        "embed": w((V, hid)),
        "runs": {f"{r:02d}": (mamba if kind == "mamba" else attention)(n)
                 for r, (kind, _, n) in enumerate(layer_runs(config))},
        "final_norm": jnp.ones((hid,), dtype)}
    if not config.tie_word_embeddings:
        params["lm_head"] = w((V, hid))
    return params


def quantize_params(params: Params, qtype: str,
                    lm_head_qtype: Optional[str] = None,
                    targets: tuple = _QUANT_TARGETS) -> Params:
    """Pack the projections, the experts and the shared MLP (`targets`: a
    family that stacks its layers by run as this one does names its own);
    the router, the convolution, `dt_bias`, `a`, `D` and the norms stay as
    they are. With tied embeddings the head becomes a PACKED COPY of the
    table (`lm_head`): the lookup keeps its bf16 rows, a decode step reads
    the head at 4 bits."""
    from bigdl_tpu.quant import QTensor, quantize_or_dense
    from bigdl_tpu.quant.qtypes import resolve_qtype, split_mixed_qtype

    qtype, head_default = split_mixed_qtype(qtype)
    lm_head_qtype = lm_head_qtype or head_default
    spec = resolve_qtype(qtype)
    if spec.is_dense:
        return params
    out = dict(params)
    out["runs"] = {
        r: {name: quantize_or_dense(w, spec.name, name)
            if name in targets and not isinstance(w, QTensor) else w
            for name, w in run.items()} for r, run in params["runs"].items()}
    head = params.get("lm_head", params["embed"])
    lm_spec = resolve_qtype(lm_head_qtype) if lm_head_qtype else spec
    if not isinstance(head, QTensor) and not lm_spec.is_dense:
        out["lm_head"] = quantize_or_dense(head, lm_spec.name, "lm_head")
    return out


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_paged_cache(config: ModelConfig, n_pages: int, page_size: int,
                     batch: int, max_pages_per_row: int
                     ) -> kvhybrid.HybridCache:
    """The family's PAGED cache for `InferenceEngine(paged=True)`: pages of
    keys and values for the attention layers and one state row a slot for
    the Mamba layers (`kvhybrid`)."""
    _, _, N, inner, C = dims(config)
    return kvhybrid.init_hybrid(
        n_layers(config, "attention"), n_layers(config, "mamba"), n_pages,
        page_size, config.num_key_value_heads, config.head_dim_, batch,
        max_pages_per_row, C, config.mamba_d_conv, (inner, N),
        counts=("state_chunks", config.mamba_chunk_size))  # the SSD form's


PAGED_CACHE_KIND = kvhybrid.KIND


def init_cache(config: ModelConfig, batch: int, cache_len: int = 0,
               quantize_kv: bool = False, paged=None
               ) -> kvhybrid.HybridCache:
    """`generate_tokens`' family hook: every row's pages in order (`paged`:
    another family's `init_paged_cache`)."""
    if quantize_kv:
        raise NotImplementedError(
            f"quantize_kv is not available for {kvhybrid.KIND} "
            f"({config.model_type}): fp8 pages beside a float32 state are "
            "not wired")
    per_row = max(-(-cache_len // GENERATE_PAGE), 1)
    cache = (paged or init_paged_cache)(
        config, batch * per_row + 1, GENERATE_PAGE, batch, per_row)
    table = 1 + jnp.arange(batch * per_row, dtype=jnp.int32)
    return dataclasses.replace(
        cache, block_tables=table.reshape(batch, per_row))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _router(config: ModelConfig, xc, p):
    """Top-k of the router's LOGITS, gates = softmax over the chosen ones,
    float32 at full precision (llama's router says why)."""
    logits = jnp.einsum(
        "bth,eh->bte", xc.astype(jnp.float32),
        p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    topv, topi = jax.lax.top_k(logits, config.num_experts_per_tok)
    return jax.nn.softmax(topv, axis=-1), topi


def _keep_codes_out(group: Params, kind: str) -> tuple[Params, dict]:
    """`deepseek._keep_codes_out` for this family's groups: the packed
    codes of every weight that goes to a kernel taken out of what a scan
    slices; the body hands the whole stack back with the layer's index."""
    from bigdl_tpu.ops.linear import grouped_route, stacks_out

    names = [n for n in _MIXER_STACKS[kind] + _SHARED_STACKS
             if n in group and grouped_route(group[n]) is None]
    if "w_up_e" in group and llama.moe_grouped_why_not(group, False) is None:
        names += [n for n in llama._EXPERT_STACKS if n in group]
    return stacks_out(group, names)


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, T] int32
    cache: Optional[kvhybrid.HybridCache],
    mode: str = "prefill",
    compute_dtype=jnp.bfloat16,
    last_logits_only: bool = False,
    moe_routing: bool = False,  # static: also return every layer's top-k
    # expert ids [L, B, T, k] int32, in the model's layer order
):
    """Returns (logits [B, T, V] float32, the cache with `pos` advanced),
    and the routing third when asked. `cache` None runs a whole sequence
    from nothing and keeps nothing."""
    from bigdl_tpu.ops import routes
    from bigdl_tpu.ops.attention import attention
    from bigdl_tpu.ops.pallas import use_pallas, why_not_pallas

    assert mode in ("prefill", "decode")
    B, T = tokens.shape
    H, P, N, inner, C = dims(config)
    Hq, Hkv, D = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim_)
    eps, rs = config.rms_norm_eps, config.residual_scale or 1.0
    scale = config.attn_scale or D ** -0.5
    decode = mode == "decode" and T == 1

    fresh = cache is None
    if fresh:
        with scope("engine"):
            cache = init_cache(config, B, T)

    with scope("engine"):
        slots = cache.pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    detail = f"mode={mode} B{B} T{T}"
    use_kernel = decode and use_pallas()
    use_flash = T > 1 and B == 1 and use_pallas()
    if use_kernel:
        routes.note("attention", "pallas:paged", detail + " nope")
        with scope("attn"):
            row_live = kvpaged.live_rows(cache)
    elif use_flash:
        routes.note("attention", "pallas:flash", detail + " nope")
    else:
        routes.note("attention", "xla",
                    f"{detail} nope ({why_not_pallas() or 'B > 1'})")
        with scope("attn"):  # the mask, once for every layer
            sj = jnp.arange(cache.max_len)
            mask = ((sj[None, None, :] <= slots[..., None])
                    & (sj[None, None, :] >= cache.start[:, None, None]))
            mask = mask[:, None, None]  # [B, 1, 1, T, S]

    with scope("engine"):
        h = llama.embed_tokens(config, params, tokens, compute_dtype)

    def attn_mixer(x, p, c, idx, proj):
        """GQA without positions over layer `idx`'s pages."""
        with scope("attn.proj"):
            q = proj(x, p, "wq").reshape(B, T, Hq, D)
            k = proj(x, p, "wk").reshape(B, T, Hkv, D)
            v = proj(x, p, "wv").reshape(B, T, Hkv, D)
        kv = kvpaged.update_layer(c.kv, idx, k, v)
        c = dataclasses.replace(c, k=kv.k, v=kv.v)
        if use_kernel:
            from bigdl_tpu.ops.pallas import paged_decode_attention

            out = paged_decode_attention(
                q[:, 0], c.k, c.v, c.block_tables, idx, c.pos, c.start,
                scale=scale, live=row_live)[:, None]
        else:
            kf, vf = kvpaged.read_layer(kv, idx, compute_dtype)
            if use_flash:
                from bigdl_tpu.ops.pallas import flash_attention

                out = flash_attention(q, kf, vf, start=c.start,
                                      q_offset=c.pos[0], scale=scale)
            else:
                out = attention(q, kf, vf, mask=mask, scale=scale)
        with scope("attn.proj"):
            return proj(out.reshape(B, T, Hq * D).astype(compute_dtype), p,
                        "wo"), c

    def mamba_mixer(x, p, c, idx, proj):
        zxd = proj(x, p, "w_in")  # [B, T, inner + C + H]
        z, xbc = zxd[..., :inner], zxd[..., inner:inner + C]
        dt = jax.nn.softplus(zxd[..., inner + C:].astype(jnp.float32)
                             + p["dt_bias"])
        y, c = kvhybrid.mix(
            c, idx, xbc, dt, -p["a"].astype(jnp.float32), p["D"],
            p["conv_w"], p["conv_b"], n_heads=H, d_head=P, d_state=N,
            chunk=config.mamba_chunk_size, decode=decode)
        y = y.reshape(B, T, inner) * jax.nn.silu(z.astype(jnp.float32))
        y = rms_norm(y, p["mixer_norm"], eps).astype(compute_dtype)
        return proj(y, p, "w_out"), c

    def layer(kind, hidden, c, p, codes, idx, at):
        """One decoder layer: number `idx` of its run (which finds it in
        the unsliced codes) and number `at` of its kind (in the cache)."""
        p = stacks_in(p, codes)

        def proj(x, p, name):
            return linear(x, p[name], None, compute_dtype,
                          layer=idx if name in codes else None)

        with scope("norm"):
            x = rms_norm(hidden, p["attn_norm"], eps)
        with scope("attn" if kind == "attention" else "mamba2"):
            out, c = (attn_mixer if kind == "attention" else mamba_mixer)(
                x, p, c, at, proj)
        with scope("norm"):
            hidden = hidden + out * rs
            x = rms_norm(hidden, p["mlp_norm"], eps).astype(compute_dtype)
        with scope("ffn"):
            topi = jnp.zeros((B, T, max(config.num_experts_per_tok, 1)),
                             jnp.int32)
            d = 0.0
            if config.is_moe:
                with scope("moe.router"):
                    topv, topi = _router(config, x, p)
                d = llama._moe_dispatch(
                    config, x, p, compute_dtype, topv, topi,
                    layer=idx if "w_up_e" in codes else None)
            if "w_up_s" in p:
                with scope("moe.shared"):  # the always-on MLP
                    g, u = proj(x, p, "w_gate_s"), proj(x, p, "w_up_s")
                    d = d + proj(jax.nn.silu(g) * u, p, "w_down_s")
        with scope("norm"):  # the add fuses with the next norm
            return hidden + d * rs, c, topi

    routing = []
    c = cache
    with scope("engine"):
        zero = jnp.zeros((), jnp.int32)
    for (kind, first, n), r in zip(layer_runs(config),
                                   sorted(params["runs"])):
        sliced, codes = _keep_codes_out(params["runs"][r], kind)
        if n == 1:
            with scope("engine"):  # the one layer out of its stack
                p1, at = jax.tree.map(lambda a: a[0], sliced), zero + first
            h, c, topi = layer(kind, h, c, p1, codes, zero, at)
            with scope("engine"):
                routing.append(topi[None])
            continue

        def body(carry, p, kind=kind, codes=codes, first=first):
            hidden, c, idx = carry
            with scope("engine"):  # the loop's own counts
                at = idx + first
            hidden, c, topi = layer(kind, hidden, c, p, codes, idx, at)
            with scope("engine"):
                return (hidden, c, idx + 1), topi if moe_routing else None

        (h, c, _), topi = jax.lax.scan(body, (h, c, zero), sliced)
        routing.append(topi)

    with scope("lm_head"):
        if last_logits_only:
            h = h[:, -1:]
        logits = llama.lm_head_logits(config, params, h, compute_dtype)
    with scope("engine"):
        extra = (jnp.concatenate(routing, axis=0),) if moe_routing else ()
        if fresh:
            return (logits, None) + extra
        return (logits, kvhybrid.advance(c, T)) + extra
