"""MiniCPM-SALA (`minicpm_sala`, openbmb/MiniCPM-SALA): MiniCPM's scaled
residual stream over layers that are, by `mixer_types`, block-sparse softmax
attention (`minicpm4`) or lightning attention (`lightning-attn`), each
followed by a dense SwiGLU.

With H the hidden size, `c = scale_depth / sqrt(layers)`:

    h = embed(tokens) * scale_emb
    per layer:  h = h + c * mixer(rmsnorm(h));  h = h + c * swiglu(rmsnorm(h))
    logits = lm_head(rmsnorm(h) / (H / dim_model_base))

Lightning mixer (32 heads of 128 with their own keys and values): q, k, v
projections; per-head RMSNorm on q and k (`qk_norm`); rope on q and k (HF's
half split, all lanes, the token's absolute index); the recurrence of
`kvsparse.py` with the fixed decay of the head; per-head RMSNorm on the
output (`use_output_norm`); a sigmoid gate from the layer's input
(`use_output_gate`); `wo`. Sparse mixer (32 query / 2 KV heads of 128, NO
rope): q, k, v; `qk_norm`; the attention and the selection of `kvsparse.py`;
the same gate (`attn_use_output_gate`); `wo`. The forms of the gates and of
the output norm, and the decay slopes, are the family's conventions written
from memory of the source (bench/configs/minicpm-sala-int4.json `assumed`).

Layout, as `granitemoehybrid.py`: `forward` walks `mixer_types` as RUNS of
layers of one kind, `params["runs"]["00"]`, `["01"]`, ... stack each run's
layers, a scan takes a whole stack and nothing is sliced out of a larger
one. The head's rows are padded to whole lane tiles (73448 -> 73472) so that
it takes the packed kernel; the logits are sliced back to the vocabulary.

The cache is `kvsparse.SparseCache`. `InferenceEngine(paged=True)` gets it
from `init_paged_cache` (through its kind, `kvsparse.CACHE_KIND`);
`generate_tokens` gets one from `init_cache` with every row's pages in order
(`TpuModel.generate` refuses the family by name: left padding would stand
inside the pooled windows).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import kvpaged, kvsparse
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.obs.scopes import scope
from bigdl_tpu.ops import linear, rms_norm
from bigdl_tpu.ops.linear import stacks_in

Params = dict[str, Any]

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
_MIXER = ("wq", "wk", "wv", "wg", "wo")
_MLP = ("w_gate", "w_up", "w_down")
_QUANT_TARGETS = _MIXER + _MLP
LIGHTNING_CHUNK = 256  # tokens of one chunk of the prefill's form
PAGED_CACHE_KIND = kvsparse.KIND
#: `TpuModel.generate*` pad prompts on the LEFT: the padding would stand
#: inside the pooled windows of the sparse layers' selection
GENERATE_REFUSAL = (
    "its sparse layers pool keys in windows from a row's first slot, which "
    "left padding would fill: serve it through InferenceEngine(paged=True)")


def layer_runs(config: ModelConfig) -> list[tuple[str, int, int]]:
    """`mixer_types` as runs: (kind, index of the run's first layer AMONG
    ITS KIND, length)."""
    runs, seen = [], {SPARSE: 0, LIGHTNING: 0}
    for kind in config.mixer_types:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen[kind], 1])
        seen[kind] += 1
    return [tuple(r) for r in runs]


def n_layers(config: ModelConfig, kind: str) -> int:
    return sum(k == kind for k in config.mixer_types)


def head_rows(config: ModelConfig) -> int:
    """Rows of the head as stored: the vocabulary in whole lane tiles."""
    return -(-config.vocab_size // 128) * 128


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16,
                scale: float = 0.02) -> Params:
    hid, V, I = config.hidden_size, config.vocab_size, config.intermediate_size
    D = config.head_dim_
    QD, KD = config.q_dim, config.kv_dim
    LD = config.lightning_heads * config.lightning_head_dim
    keys = iter(jax.random.split(key, 16 * (len(layer_runs(config)) + 1)))

    def w(shape, std=scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dtype)

    def run(kind, n):
        qd, kd, d = (QD, KD, D) if kind == SPARSE else (
            LD, LD, config.lightning_head_dim)
        out = {"attn_norm": jnp.ones((n, hid), dtype),
               "mlp_norm": jnp.ones((n, hid), dtype),
               "q_norm": jnp.ones((n, d), dtype),
               "k_norm": jnp.ones((n, d), dtype),
               "wq": w((n, qd, hid)), "wk": w((n, kd, hid)),
               "wv": w((n, kd, hid)), "wg": w((n, qd, hid)),
               "wo": w((n, hid, qd)), "w_gate": w((n, I, hid)),
               "w_up": w((n, I, hid)), "w_down": w((n, hid, I))}
        if kind == LIGHTNING:
            out["o_norm"] = jnp.ones((n, d), dtype)
        return out

    params: Params = {
        "embed": w((V, hid)),
        "runs": {f"{r:02d}": run(kind, n)
                 for r, (kind, _, n) in enumerate(layer_runs(config))},
        "final_norm": jnp.ones((hid,), dtype)}
    if not config.tie_word_embeddings:
        params["lm_head"] = w((head_rows(config), hid))
    return params


def quantize_params(params: Params, qtype: str,
                    lm_head_qtype: Optional[str] = None) -> Params:
    """Pack the mixers' projections, the MLPs and the head; the norms and
    the embedding stay as they are."""
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
            if name in _QUANT_TARGETS and not isinstance(w, QTensor) else w
            for name, w in run.items()} for r, run in params["runs"].items()}
    head = params.get("lm_head")
    if head is None:  # tied: a packed copy of the table, rows padded
        head = jnp.pad(params["embed"], (
            (0, -params["embed"].shape[0] % 128), (0, 0)))
    lm_spec = resolve_qtype(lm_head_qtype) if lm_head_qtype else spec
    if not isinstance(head, QTensor) and not lm_spec.is_dense:
        out["lm_head"] = quantize_or_dense(head, lm_spec.name, "lm_head")
    return out


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_paged_cache(config: ModelConfig, n_pages: int, page_size: int,
                     batch: int, max_pages_per_row: int
                     ) -> kvsparse.SparseCache:
    sz = kvsparse.Sizes.of(config)
    if sz.block != page_size:
        raise NotImplementedError(
            f"{config.model_type}: a selection block of {sz.block} tokens "
            f"is served from pages of {sz.block}, not {page_size} "
            "(a block of the model is a page of the pool)")
    D = config.lightning_head_dim
    return kvsparse.init_sparse(
        n_layers(config, SPARSE), n_layers(config, LIGHTNING), n_pages,
        page_size, config.num_key_value_heads, config.head_dim_, batch,
        max_pages_per_row, config.lightning_heads * D, D, sz.stride, sz.topk,
        report_ids=kvsparse.CACHE_KIND.report_ids)


def init_cache(config: ModelConfig, batch: int, cache_len: int = 0,
               quantize_kv: bool = False) -> kvsparse.SparseCache:
    """`generate_tokens`' family hook: every row's pages in order."""
    if quantize_kv:
        raise NotImplementedError(
            f"quantize_kv is not available for {kvsparse.KIND} "
            f"({config.model_type}): fp8 pages under a selection are not "
            "wired")
    page = kvsparse.Sizes.of(config).block
    per_row = max(-(-cache_len // page), 1)
    cache = init_paged_cache(config, batch * per_row + 1, page, batch,
                             per_row)
    table = 1 + jnp.arange(batch * per_row, dtype=jnp.int32)
    return dataclasses.replace(
        cache, block_tables=table.reshape(batch, per_row))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _keep_codes_out(group: Params) -> tuple[Params, dict]:
    from bigdl_tpu.ops.linear import grouped_route, stacks_out

    return stacks_out(group, [n for n in _QUANT_TARGETS
                              if grouped_route(group[n]) is None])


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, T] int32
    cache: Optional[kvsparse.SparseCache],
    mode: str = "prefill",
    compute_dtype=jnp.bfloat16,
    last_logits_only: bool = False,
    logits_at=None,  # traced position: the head on that one position only
    # (the engine's prefill wants the last TOKEN's logits: [T, V] at T =
    # 16384 would be 4.8 GB)
):
    """Returns (logits [B, T, V] float32, the cache with `pos` advanced and
    `report` holding the sparse layers' counts, and what they chose where
    the report has room for it). `cache` None runs a
    whole sequence from nothing and keeps nothing. A prefill (T > 1) runs
    from an EMPTY row (`cache.pos` 0)."""
    from bigdl_tpu.ops import routes
    from bigdl_tpu.ops.attention import attention
    from bigdl_tpu.ops.pallas import use_pallas, why_not_pallas
    from bigdl_tpu.ops.rope import (
        apply_rotary_emb, default_inv_freq, rope_cos_sin,
    )

    assert mode in ("prefill", "decode")
    B, T = tokens.shape
    Hq, Hkv, D = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim_)
    LH, LD = config.lightning_heads, config.lightning_head_dim
    eps, rs = config.rms_norm_eps, config.residual_scale or 1.0
    scale = D ** -0.5
    sz = kvsparse.Sizes.of(config)
    decode = mode == "decode" and T == 1

    fresh = cache is None
    if fresh:
        with scope("engine"):
            cache = init_cache(config, B, T)

    detail = f"mode={mode} B{B} T{T}"
    why_xla = kvsparse.why_not_sparse_kernel(Hkv, D, 2)
    use_kernel = decode and why_xla is None
    use_flash = T > 1 and B == 1 and use_pallas()
    if decode:
        routes.note("attention", "pallas:paged_sparse" if use_kernel else
                    "xla", detail + " nope" + (
                        "" if use_kernel else f" ({why_xla})"))
        routes.note("sparse", "selection", (
            f"block {sz.block} = page, top {sz.topk}, window "
            f"{sz.window_blocks * sz.block}, dense under {sz.dense_len}, "
            "union list"))
    else:
        routes.note("attention", "pallas:flash" if use_flash else "xla",
                    f"{detail} nope " + (
                        "dense" if T < sz.dense_len else
                        "masked by selection from query "
                        f"{(sz.dense_len - 1) // kvsparse.QUERY_CHUNK * kvsparse.QUERY_CHUNK}")
                    + ("" if use_flash else
                       f" ({why_not_pallas() or 'B > 1'})"))
    with scope("engine"):
        live = kvpaged.live_rows(cache)
        positions = jnp.maximum(
            cache.pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
            - cache.start[:, None], 0)
    with scope("attn.rope"):
        cos, sin = rope_cos_sin(
            positions, default_inv_freq(LD, config.rope_theta))
    with scope("engine"):
        h = llama.embed_tokens(config, params, tokens, compute_dtype)

    def attend(q, k, v, mask=None):
        """Causal attention over this forward's own keys, from slot
        `start`; `mask [B, Hkv, T, T]` what a selection lets a query read
        beside."""
        if use_flash:
            from bigdl_tpu.ops.pallas import flash_attention

            return flash_attention(q.astype(compute_dtype), k, v,
                                   start=cache.start, scale=scale, mask=mask)
        sj = jnp.arange(T)
        ok = ((sj[None, None, :] <= sj[None, :, None])
              & (sj[None, None, :] >= cache.start[:, None, None]))[:, None]
        if mask is not None:
            ok = ok & (mask != 0)
        return attention(q.astype(compute_dtype), k, v,
                         mask=ok[:, :, None], scale=scale)

    def sparse_mixer(x, p, c, idx, proj):
        with scope("attn.proj"):
            q = proj(x, p, "wq").reshape(B, T, Hq, D)
            k = proj(x, p, "wk").reshape(B, T, Hkv, D)
            v = proj(x, p, "wv").reshape(B, T, Hkv, D)
            g = proj(x, p, "wg")
        with scope("attn.rope"):  # the norms; no rope on these layers
            q = rms_norm(q, p["q_norm"], eps)
            k = rms_norm(k, p["k_norm"], eps)
        if decode:
            o, c, ids, counts = kvsparse.sparse_decode_layer(
                c, idx, q[:, 0], k[:, 0], v[:, 0], scale, sz, use_kernel,
                live)
            o = o[:, None]
        else:
            o, c, ids, counts = kvsparse.sparse_prefill_layer(
                c, idx, q, k, v, scale, sz, attend)
        with scope("engine"):
            c = kvsparse.put_report(c, idx, ids, counts)
        with scope("attn.gate"):
            o = (o.reshape(B, T, Hq * D).astype(jnp.float32)
                 * jax.nn.sigmoid(g.astype(jnp.float32)))
        with scope("attn.proj"):
            return proj(o.astype(compute_dtype), p, "wo"), c

    def lightning_mixer(x, p, c, idx, proj):
        with scope("attn.proj"):
            q = proj(x, p, "wq").reshape(B, T, LH, LD)
            k = proj(x, p, "wk").reshape(B, T, LH, LD)
            v = proj(x, p, "wv").reshape(B, T, LH, LD)
            g = proj(x, p, "wg")
        with scope("attn.rope"):
            q = rms_norm(q, p["q_norm"], eps)
            k = rms_norm(k, p["k_norm"], eps)
            q, k = apply_rotary_emb(q, k, cos, sin)
        o, c = kvsparse.lightning_mix(
            c, idx, q, k, v, chunk=LIGHTNING_CHUNK, decode=decode)
        with scope("attn.gate"):
            o = rms_norm(o, p["o_norm"], eps).astype(jnp.float32)
            o = (o.reshape(B, T, LH * LD)
                 * jax.nn.sigmoid(g.astype(jnp.float32)))
        with scope("attn.proj"):
            return proj(o.astype(compute_dtype), p, "wo"), c

    def layer(kind, hidden, c, p, codes, idx, at):
        p = stacks_in(p, codes)

        def proj(x, p, name):
            return linear(x, p[name], None, compute_dtype,
                          layer=idx if name in codes else None)

        with scope("norm"):
            x = rms_norm(hidden, p["attn_norm"], eps)
        with scope("attn"):
            out, c = (sparse_mixer if kind == SPARSE else lightning_mixer)(
                x, p, c, at, proj)
        with scope("norm"):
            hidden = hidden + out * rs
            x = rms_norm(hidden, p["mlp_norm"], eps).astype(compute_dtype)
        with scope("ffn"):
            d = proj(jax.nn.silu(proj(x, p, "w_gate")) * proj(x, p, "w_up"),
                     p, "w_down")
        with scope("norm"):
            return hidden + d * rs, c

    with scope("engine"):
        c = kvsparse.clear_counts(cache)
        zero = jnp.zeros((), jnp.int32)
    for (kind, first, n), r in zip(layer_runs(config),
                                   sorted(params["runs"])):
        sliced, codes = _keep_codes_out(params["runs"][r])
        if n == 1:
            with scope("engine"):
                p1, at = jax.tree.map(lambda a: a[0], sliced), zero + first
            h, c = layer(kind, h, c, p1, codes, zero, at)
            continue
        def body(carry, p, kind=kind, codes=codes, first=first):
            hidden, c, idx = carry
            with scope("engine"):
                at = idx + first
            hidden, c = layer(kind, hidden, c, p, codes, idx, at)
            with scope("engine"):
                return (hidden, c, idx + 1), None

        (h, c, _), _ = jax.lax.scan(body, (h, c, zero), sliced)

    with scope("lm_head"):
        if logits_at is not None:
            h = jax.lax.dynamic_slice_in_dim(h, logits_at, 1, axis=1)
        elif last_logits_only:
            h = h[:, -1:]
        logits = llama.lm_head_logits(config, params, h, compute_dtype)
        logits = logits[..., :config.vocab_size]
    with scope("engine"):
        if fresh:
            return logits, None
        return logits, kvsparse.advance(c, T)
