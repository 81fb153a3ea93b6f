"""Generation: jitted prefill + on-device decode loop.

The reference rides HF `GenerationMixin.generate` — a host-side Python
token loop launching one eager kernel per op (SURVEY.md §3.2). The
TPU-native design compiles the whole decode loop into one XLA program:
`lax.while_loop` carrying the KV cache, with on-device sampling
(greedy / temperature / top-k / top-p) and early exit when every row hit
EOS. Host↔device traffic is two transfers total (prompt in, tokens out).

Prompt lengths are bucketed (powers of two) so at most O(log S) prefill
programs are ever compiled per model.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import kvcache
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.obs.scopes import scope


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 32
    do_sample: bool = False
    temperature: float = 1.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    repetition_penalty: float = 1.0  # HF semantics: >1 discourages repeats
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0


def apply_repetition_penalty(
    logits: jax.Array,  # [B, V]
    seen: jax.Array,  # [B, V] bool: token appeared in prompt or output
    penalty,  # float or [B] traced
) -> jax.Array:
    """HF RepetitionPenaltyLogitsProcessor semantics (the reference fuses
    this as xe_addons.repetition_penalty_logits_process_inplaced): seen
    tokens' scores divide by the penalty when positive, multiply when
    negative."""
    p = jnp.asarray(penalty, logits.dtype)
    if p.ndim == 1:
        p = p[:, None]
    penalized = jnp.where(logits < 0, logits * p, logits / p)
    return jnp.where(seen, penalized, logits)


def seen_from_prompt(tokens: jax.Array, start: jax.Array, vocab: int) -> jax.Array:
    """[B, V] bool presence mask over the real (non-pad) prompt tokens."""
    B, T = tokens.shape
    real = jnp.arange(T)[None, :] >= start[:, None]
    idx = jnp.where(real, tokens, vocab)  # pads land in the overflow bin
    return (
        jnp.zeros((B, vocab + 1), jnp.bool_)
        .at[jnp.arange(B)[:, None], idx].set(True)[:, :vocab]
    )


def sample_token(
    logits: jax.Array,  # [B, V] float32
    key: jax.Array,
    gen: GenerationConfig,
) -> jax.Array:
    """On-device sampling; gen is static so dead branches compile away."""
    if not gen.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / max(gen.temperature, 1e-5)
    if gen.top_k is not None:
        kth = jax.lax.top_k(logits, gen.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if gen.top_p is not None:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1) - probs
        cutoff_idx = jnp.sum(cum < gen.top_p, axis=-1, keepdims=True) - 1
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def filter_logits_per_row(
    logits: jax.Array,  # [B, ..., V] float32
    temperature: jax.Array,  # [B] float32
    top_k: jax.Array,  # [B] int32, <=0 disables
    top_p: jax.Array,  # [B] float32, >=1 disables
) -> jax.Array:
    """Temperature + top-k + top-p filtering with traced per-row params;
    returns masked/scaled logits whose softmax is the exact sampling
    distribution (shared by sample_token_per_row and the speculative
    rejection-acceptance path, which needs the DISTRIBUTION, not just a
    sample). Extra middle axes broadcast (verify rounds pass [B, K, V])."""
    V = logits.shape[-1]
    exp = (slice(None),) + (None,) * (logits.ndim - 1)
    lt = logits / jnp.maximum(temperature, 1e-5)[exp]
    sorted_desc = jnp.sort(lt, axis=-1)[..., ::-1]
    # top-k first: threshold at the k-th largest value per row
    kth = jnp.take_along_axis(
        sorted_desc, jnp.clip(top_k - 1, 0, V - 1)[exp], axis=-1
    )
    lt_k = jnp.where((top_k > 0)[exp] & (lt < kth), -jnp.inf, lt)
    # top-p (nucleus) over the top-k-FILTERED, renormalized
    # distribution (HF order; matches sample_token): -inf survivors
    # sort last and carry zero probability
    sorted_k = jnp.sort(lt_k, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_k, axis=-1)
    cum = jnp.cumsum(probs, axis=-1) - probs
    cutoff_idx = jnp.sum(cum < top_p[exp], axis=-1, keepdims=True) - 1
    cutoff = jnp.take_along_axis(
        sorted_k, jnp.clip(cutoff_idx, 0, V - 1), axis=-1
    )
    return jnp.where((top_p < 1.0)[exp] & (lt_k < cutoff), -jnp.inf, lt_k)


def sample_token_per_row(
    logits: jax.Array,  # [B, V] float32
    key: jax.Array,
    temperature: jax.Array,  # [B] float32
    top_k: jax.Array,  # [B] int32, <=0 disables
    top_p: jax.Array,  # [B] float32, >=1 disables
    do_sample: jax.Array,  # [B] bool
) -> jax.Array:
    """Per-row sampling with TRACED parameters — every row of a batch can
    carry its own temperature/top-k/top-p (the serving engine's
    per-request sampling; the reference serves one sampling config per
    worker, model_worker.py:28-200, so this exceeds it). Rows with
    do_sample=False take the plain argmax.
    """
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def run_sampling(_):
        masked = filter_logits_per_row(logits, temperature, top_k, top_p)
        return jax.random.categorical(key, masked, axis=-1).astype(jnp.int32)

    # all-greedy batches (the serving engine's common case) skip the
    # full-vocab sort/softmax entirely
    sampled = jax.lax.cond(
        jnp.any(do_sample), run_sampling, lambda _: greedy, operand=None
    )
    return jnp.where(do_sample, sampled, greedy)


def pad_prompts(
    prompts: Sequence[Sequence[int]], pad_id: int, bucket: Optional[int] = None
) -> tuple[np.ndarray, np.ndarray]:
    """Left-pad a ragged batch to a power-of-two bucket.

    Returns (tokens [B, T], start [B]) — `start[b]` = number of pad slots,
    feeding KVCache's validity mask. Left-padding keeps every row's last
    prompt token at index T-1, so prefill logits need no gather.
    """
    maxlen = max(len(p) for p in prompts)
    if bucket is None:
        bucket = 16
        while bucket < maxlen:
            bucket *= 2
    assert bucket >= maxlen
    b = len(prompts)
    tokens = np.full((b, bucket), pad_id, np.int32)
    start = np.zeros((b,), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, bucket - len(p):] = np.asarray(p, np.int32)
        start[i] = bucket - len(p)
    return tokens, start


@functools.partial(
    jax.jit,
    static_argnames=(
        "config", "gen", "model_forward", "cache_len", "quantize_kv",
        "compress_budget", "compress_window", "compress_kernel",
        "last_logits", "cache_init", "streaming",
    ),
    donate_argnames=(),
)
def generate_tokens(
    config: ModelConfig,
    params,
    tokens: jax.Array,  # [B, T] left-padded prompt
    start: jax.Array,  # [B]
    key: jax.Array,
    gen: GenerationConfig,
    model_forward,  # static: the family forward fn (models.llama.forward)
    cache_len: int,
    quantize_kv: bool = False,
    compress_budget: int = 0,  # SnapKV: compress prompt KV to this many slots
    compress_window: int = 32,
    compress_kernel: int = 7,
    # lm head on the last prefill position only (BIGDL_TPU_LAST_LM_HEAD;
    # reference IPEX_LLM_LAST_LM_HEAD) — saves the [B,T,V] prefill logits
    last_logits: bool = True,
    # family cache-init hook: fn(config, B, cache_len, quantize_kv) for
    # architectures whose state is not a KV cache (rwkv's RwkvState);
    # None = standard kvcache.init_cache
    cache_init=None,
    # (sink, window) or (sink, window, chunk) attention-sink streaming:
    # the cache is `window` slots and the oldest `chunk` non-sink slots
    # are evicted together once full (bigdl_tpu/streaming.py) —
    # generation length becomes unbounded
    streaming=None,
) -> jax.Array:
    """One compiled program: prefill + full decode loop.

    With compress_budget > 0 the prompt KV is SnapKV-compressed after
    prefill (reference DynamicCompressCache, kv.py:246-375) and the decode
    loop runs on the compact cache — less HBM traffic per token and a
    cache whose size is independent of prompt length.

    Returns [B, max_new_tokens] generated ids (pad_token_id after EOS).
    """
    from bigdl_tpu.utils import cache_len_for

    B, T = tokens.shape
    shift = None
    if streaming is not None:
        from bigdl_tpu.streaming import default_chunk, make_sink_shift

        sink, window = streaming[:2]
        chunk = streaming[2] if len(streaming) > 2 else default_chunk(window, sink)
        assert cache_len == window and cache_len > T
        assert not quantize_kv and compress_budget == 0 and cache_init is None
        shift = make_sink_shift(config, window, sink, chunk)
    else:
        assert cache_len >= T + gen.max_new_tokens
    with scope("engine"):
        if cache_init is not None:
            cache = cache_init(config, B, cache_len, quantize_kv)
            assert compress_budget == 0, "SnapKV needs a KV cache"
        else:
            cache = kvcache.init_cache(
                config.num_hidden_layers, B, cache_len,
                config.num_key_value_heads, config.head_dim_,
                quantize_kv=quantize_kv,
            )
        cache = dataclasses.replace(cache, start=start)

    if compress_budget:
        assert compress_budget > compress_window
        logits, cache, obs = model_forward(
            config, params, tokens, cache, mode="prefill",
            collect_obs=compress_window, last_logits_only=last_logits,
        )
        out_len = cache_len_for(compress_budget, gen.max_new_tokens)
        with scope("engine"):
            cache = kvcache.compress(
                cache, obs, compress_budget, out_len,
                window=compress_window, kernel=compress_kernel,
            )
    else:
        logits, cache = model_forward(
            config, params, tokens, cache, mode="prefill",
            last_logits_only=last_logits,
        )
    use_rep = gen.repetition_penalty != 1.0  # static: compiles away
    with scope("engine"):
        seen = (
            seen_from_prompt(tokens, start, config.vocab_size)
            if use_rep else jnp.zeros((B, 1), jnp.bool_)
        )
        key, k0 = jax.random.split(key)
    with scope("sample"):
        first_logits = logits[:, -1]
        if use_rep:
            first_logits = apply_repetition_penalty(
                first_logits, seen, gen.repetition_penalty
            )
        first = sample_token(first_logits, k0, gen)
    eos = gen.eos_token_id
    with scope("engine"):
        if use_rep:
            seen = seen.at[jnp.arange(B), first].set(True)

        out = jnp.full((B, gen.max_new_tokens), gen.pad_token_id, jnp.int32)
        out = out.at[:, 0].set(first)
        done = (
            first == eos if eos is not None else jnp.zeros((B,), jnp.bool_)
        )

    def cond(state):
        i, _, _, done, _, _, _ = state
        with scope("engine"):
            return (i < gen.max_new_tokens) & ~jnp.all(done)

    def step(state):
        i, cur, cache, done, out, key, seen = state
        with scope("engine"):
            if shift is not None:
                cache = shift(cache)  # evict the oldest non-sink slot if full
            cur = cur[:, None]
        logits, cache = model_forward(
            config, params, cur, cache, mode="decode"
        )
        with scope("engine"):
            key, k = jax.random.split(key)
        with scope("sample"):
            step_logits = logits[:, -1]
            if use_rep:
                step_logits = apply_repetition_penalty(
                    step_logits, seen, gen.repetition_penalty
                )
            nxt = sample_token(step_logits, k, gen)
        with scope("engine"):
            if eos is not None:
                nxt = jnp.where(done, gen.pad_token_id, nxt)
                done = done | (nxt == eos)
            if use_rep:
                seen = seen.at[jnp.arange(B), nxt].set(True)
            out = jax.lax.dynamic_update_slice(out, nxt[:, None], (0, i))
            return (i + 1, nxt, cache, done, out, key, seen)

    with scope("engine"):
        state = (jnp.ones((), jnp.int32), first, cache, done, out, key, seen)
    _, _, _, _, out, _, _ = jax.lax.while_loop(cond, step, state)
    return out
