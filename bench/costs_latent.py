"""Operations and bytes of the latent-attention layers of one decode step of
an MLA model, from its shapes: what the kernel
`paged_latent_decode_attention` must do. Beside `costs.py` (the dense
projections of a GQA model), `costs_moe.py` (routed experts) and
`costs_retention.py` (a recurrent state).

Counted as the ALGORITHM needs them, by live TOKENS and not by pages (a page
half full is read whole by the kernel; the least any kernel could do is the
tokens): per layer, every live token's latent row (`kv_lora_rank` compressed
values and `qk_rope_head_dim` rope-key values, bf16) crosses HBM once and is
shared by all heads; per layer and live slot the absorbed query [H, r + dr]
goes in and the context [H, r] comes out (bf16). Per live token, layer and
head: a score over r + dr and a value sum over r, a multiply-add each. The
up-projections around the kernel (W_uk into the query, W_uv out of the
context) are XLA's and not the kernel's."""

from __future__ import annotations

from bench import costs_moe

_BPE = 2  # bf16 latents, query and context


def latent_width(hf: dict) -> int:
    return hf["kv_lora_rank"] + hf["qk_rope_head_dim"]


def latent_token_bytes(hf: dict) -> int:
    """One token's latents over all layers: what a decode step reads of a
    live token, and what a page holds per token."""
    return hf["num_hidden_layers"] * latent_width(hf) * _BPE


def decode_cost(hf: dict, live_tokens: float, rows_live: float) -> dict:
    """One decode step's latent attention: `live_tokens` cached tokens over
    `rows_live` live slots."""
    L, H, r = (hf["num_hidden_layers"], hf["num_attention_heads"],
               hf["kv_lora_rank"])
    w = latent_width(hf)
    small = L * H * (w + r) * _BPE  # q in, context out, a live slot
    return {"bytes": live_tokens * latent_token_bytes(hf) + rows_live * small,
            "flops": live_tokens * L * H * 2 * (w + r)}


def expert_layers(hf: dict) -> int:
    return hf["num_hidden_layers"] - hf.get("first_k_dense_replace", 0)


def step_bytes(hf: dict, weight_bytes: int, experts_hit: float,
               latent_bytes: float) -> float:
    """What one decode step must move: the packed parameter tree without
    the embedding table (`weight_bytes`), less the experts nobody chose,
    plus the live latents."""
    stacks = (costs_moe.expert_bytes(hf) * hf["n_routed_experts"]
              * expert_layers(hf))
    return (weight_bytes - stacks + experts_hit * costs_moe.expert_bytes(hf)
            + latent_bytes)


def traced_steps(run) -> list:
    """Arguments of the `decode_step` spans that carry latent traffic, those
    inside the traced seconds where the run has a device trace, else the
    whole window's. Empty for a program without such spans."""
    spans = [(t, a) for t, _, a in run.span_list("decode_step")
             if a.get("latent_live_tokens") and "latent_bytes_read" in a]
    dev = run.device
    if dev is not None:
        lo, hi = dev.begin + dev.offset, dev.end + dev.offset
        inside = [(t, a) for t, a in spans if lo <= t < hi]
        spans = inside or spans
    return [a for _, a in spans]
