"""Jamba (`jamba`, e.g. ai21labs/AI21-Jamba2-3B): Mamba-1 layers with a
multi-query attention layer every `attn_layer_period`, a SwiGLU MLP after
every mixer, no position encoding anywhere.

HF's modeling_jamba is the behavioural spec (`num_experts` 1: every layer
takes `JambaMLP`). Both residuals plain:

    h = embed(tokens)
    per layer:  h = h + mixer(rmsnorm(h));  h = h + mlp(rmsnorm(h))
    logits = rmsnorm(h) @ embed^T                       (tied embeddings)

Mamba-1 mixer, inner width E = `mamba_expand` x hidden, N = `mamba_d_state`,
R = `mamba_dt_rank`: `[u | z] = in_proj(x)`; `c = silu(causal depthwise
conv(u) + b)` over the E channels alone; `[r | B | C] = x_proj(c)`, each
through its own RMSNorm (Jamba's addition to Mamba: `dt_layernorm`,
`b_layernorm`, `c_layernorm`); `dt = softplus(dt_proj(r) + b_dt)` a CHANNEL;
the selective scan of `kvhybrid.mix1` with `A = -exp(A_log)` `[N, E]`;
`out_proj(y * silu(z))`. Attention mixer: `num_attention_heads` query heads
on `num_key_value_heads` (one) KV heads, no bias, NO rotation, causal.

Layout. `forward` walks `layer_types` as RUNS of one kind, as
`granitemoehybrid.forward` does and for its reason (a scan takes a whole
stack; nothing is sliced out of a larger one): `params["runs"]["00"]`, ...
Packed: `w_in`, `w_out`, the MLP, q, k, v, o and the head's copy of the
table. `w_x` and `w_dt` stay in the init dtype (1.8 M parameters a layer at
the published sizes, feeding a softplus and an `exp`; K = 160 and O = 192 are
no shapes of the packed kernels). The decay rate is kept as
`a = exp(A_log)` `[N, E]` (A = -a) in float16, the state's own layout; the
convolution, `dt_bias` and `D` float32.

The cache is `kvhybrid.HybridCache` with this family's state: `ssm [Lm, R,
N, E]`, the channels on lanes, and the convolution's tails over E channels,
`conv [Lm, R, (K - 1) * E]`, a row's in one piece.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import kvhybrid, kvpaged
from bigdl_tpu.models import granitemoehybrid, llama
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.models.granitemoehybrid import layer_runs, n_layers
from bigdl_tpu.obs.scopes import scope
from bigdl_tpu.ops import linear, rms_norm
from bigdl_tpu.ops.linear import stacks_in

Params = dict[str, Any]

# the per-layer weights that go through `linear`, by kind of run
_MIXER_STACKS = {"mamba": ("w_in", "w_out"),
                 "attention": ("wq", "wk", "wv", "wo")}
_MLP_STACKS = ("w_gate", "w_up", "w_down")
_QUANT_TARGETS = (_MIXER_STACKS["mamba"] + _MIXER_STACKS["attention"]
                  + _MLP_STACKS)
# what `kvhybrid.mix1` reads of a Mamba layer
_MIX1 = ("conv_w", "conv_b", "w_x", "dt_norm", "b_norm", "c_norm", "w_dt",
         "dt_bias", "a", "D")


def dims(config: ModelConfig):
    """(inner width, state size, dt rank)."""
    return (config.mamba_expand * config.hidden_size, config.mamba_d_state,
            config.mamba_dt_rank)


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16,
                scale: float = 0.02) -> Params:
    """Random init (tests/benchmarks run without checkpoints). The step
    sizes and decay rates are drawn as Mamba initialises them: dt
    log-uniform in [0.001, 0.1], a[n, :] = n + 1."""
    E, N, R = dims(config)
    hid, V, I = config.hidden_size, config.vocab_size, config.intermediate_size
    K = config.mamba_d_conv
    keys = iter(jax.random.split(key, 16 * (len(layer_runs(config)) + 1)))

    def w(shape, std=scale, dt=dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dt)

    def block(n):  # what every layer has beside its mixer
        return {"attn_norm": jnp.ones((n, hid), dtype),
                "mlp_norm": jnp.ones((n, hid), dtype),
                "w_gate": w((n, I, hid)), "w_up": w((n, I, hid)),
                "w_down": w((n, hid, I))}

    def mamba(n):
        dt0 = jnp.exp(jax.random.uniform(
            next(keys), (n, E), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        a = jnp.arange(1, N + 1, dtype=jnp.float32)[None, :, None]
        return dict(
            block(n),
            w_in=w((n, 2 * E, hid)), w_out=w((n, hid, E)),
            conv_w=w((n, K, E), std=K ** -0.5, dt=jnp.float32),
            conv_b=jnp.zeros((n, E), jnp.float32),
            w_x=w((n, R + 2 * N, E)), w_dt=w((n, E, R), std=R ** -0.5),
            dt_norm=jnp.ones((n, R), dtype), b_norm=jnp.ones((n, N), dtype),
            c_norm=jnp.ones((n, N), dtype),
            dt_bias=dt0 + jnp.log(-jnp.expm1(-dt0)),  # softplus^-1(dt0)
            a=jnp.broadcast_to(a, (n, N, E)).astype(jnp.float16),
            D=jnp.ones((n, E), jnp.float32))

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
                    lm_head_qtype: Optional[str] = None) -> Params:
    """Pack the projections and the MLPs; `w_x`, `w_dt`, the convolution,
    `dt_bias`, `a`, `D` and the norms stay as they are. With tied
    embeddings the head becomes a PACKED COPY of the table (`lm_head`)."""
    return granitemoehybrid.quantize_params(params, qtype, lm_head_qtype,
                                            targets=_QUANT_TARGETS)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_paged_cache(config: ModelConfig, n_pages: int, page_size: int,
                     batch: int, max_pages_per_row: int
                     ) -> kvhybrid.HybridCache:
    """The family's PAGED cache for `InferenceEngine(paged=True)`: pages of
    keys and values for the attention layers and one state row a slot for
    the Mamba layers, the state `[N, E]` a layer (`kvhybrid`)."""
    E, N, _ = dims(config)
    return kvhybrid.init_hybrid(
        n_layers(config, "attention"), n_layers(config, "mamba"), n_pages,
        page_size, config.num_key_value_heads, config.head_dim_, batch,
        max_pages_per_row, E, config.mamba_d_conv, (N, E),
        counts=("scan_tokens",),  # a selective scan runs token by token
        conv_rows=1)


PAGED_CACHE_KIND = kvhybrid.KIND


def init_cache(config: ModelConfig, batch: int, cache_len: int = 0,
               quantize_kv: bool = False) -> kvhybrid.HybridCache:
    """`generate_tokens`' family hook: every row's pages in order."""
    return granitemoehybrid.init_cache(config, batch, cache_len, quantize_kv,
                                       paged=init_paged_cache)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _keep_codes_out(group: Params, kind: str) -> tuple[Params, dict]:
    """`granitemoehybrid._keep_codes_out` for this family's groups."""
    from bigdl_tpu.ops.linear import grouped_route, stacks_out

    names = [n for n in _MIXER_STACKS[kind] + _MLP_STACKS
             if n in group and grouped_route(group[n]) is None]
    return stacks_out(group, names)


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, T] int32
    cache: Optional[kvhybrid.HybridCache],
    mode: str = "prefill",
    compute_dtype=jnp.bfloat16,
    last_logits_only: bool = False,
):
    """Returns (logits [B, T, V] float32, the cache with `pos` advanced).
    `cache` None runs a whole sequence from nothing and keeps nothing."""
    from bigdl_tpu.ops import routes
    from bigdl_tpu.ops.attention import attention
    from bigdl_tpu.ops.pallas import use_pallas, why_not_pallas

    assert mode in ("prefill", "decode")
    B, T = tokens.shape
    E, N, R = dims(config)
    Hq, Hkv, D = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim_)
    eps, scale = config.rms_norm_eps, D ** -0.5
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
        """Multi-query attention without positions over layer `idx`'s
        pages."""
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
        uz = proj(x, p, "w_in")  # [B, T, 2 E]: [u | z]
        y, c = kvhybrid.mix1(
            c, idx, uz[..., :E], {n: p[n] for n in _MIX1}, dt_rank=R,
            d_state=N, eps=eps, decode=decode)
        y = y * jax.nn.silu(uz[..., E:].astype(jnp.float32))
        return proj(y.astype(compute_dtype), p, "w_out"), c

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
            hidden = hidden + out
            x = rms_norm(hidden, p["mlp_norm"], eps).astype(compute_dtype)
        with scope("ffn"):
            g, u = proj(x, p, "w_gate"), proj(x, p, "w_up")
            d = proj(jax.nn.silu(g) * u, p, "w_down")
        with scope("norm"):  # the add fuses with the next norm
            return hidden + d, c

    c = cache
    with scope("engine"):
        zero = jnp.zeros((), jnp.int32)
    for (kind, first, n), r in zip(layer_runs(config),
                                   sorted(params["runs"])):
        sliced, codes = _keep_codes_out(params["runs"][r], kind)
        if n == 1:
            with scope("engine"):  # the one layer out of its stack
                p1, at = jax.tree.map(lambda a: a[0], sliced), zero + first
            h, c = layer(kind, h, c, p1, codes, zero, at)
            continue

        def body(carry, p, kind=kind, codes=codes, first=first):
            hidden, c, idx = carry
            with scope("engine"):  # the loop's own counts
                at = idx + first
            hidden, c = layer(kind, hidden, c, p, codes, idx, at)
            with scope("engine"):
                return (hidden, c, idx + 1), None

        (h, c, _), _ = jax.lax.scan(body, (h, c, zero), sliced)

    with scope("lm_head"):
        if last_logits_only:
            h = h[:, -1:]
        logits = llama.lm_head_logits(config, params, h, compute_dtype)
    with scope("engine"):
        if fresh:
            return logits, None
        return logits, kvhybrid.advance(c, T)
