"""Speculative decoding INSIDE the continuous-batching engine, composed
with the paged KV pool + prefix cache — the serving configuration the
reference reaches through its vLLM fork + speculative worker
(serving/fastchat/ipex_llm_worker.py, vllm/xpu/model_convert.py).

Greedy requests emit the target model's exact tokens (byte-identical to
plain serving); sampling requests accept drafts by rejection sampling,
so their output law is exactly plain sampling too.

    python examples/speculative_serving.py
"""

import jax

from bigdl_tpu.api import TpuModel, optimize_model
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import PRESETS


def main():
    cfg = PRESETS["tiny-llama"]
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    # bf16 target: the sym_int4 self-draft then differs from the target
    # (a quantized target would draft with identical weights — all cost,
    # no speedup; pass draft_params= for an external draft model)
    model = TpuModel(cfg, optimize_model(params, cfg, low_bit="bf16"), "bf16")

    from bigdl_tpu.serving.engine import InferenceEngine

    engine = InferenceEngine(
        model, n_slots=4, max_len=256,
        paged=True, page_size=32,        # paged pool + prefix cache
        speculative=True, draft_k=4,     # draft-4-verify-1 rounds
    )
    shared = list(range(40, 72))  # a shared system-prompt prefix
    reqs = [
        engine.submit(shared + [3, 1, 4], max_new_tokens=24),
        engine.submit(shared + [9, 2, 6], max_new_tokens=24),
        engine.submit(shared + [5, 3], max_new_tokens=24,
                      do_sample=True, temperature=0.8),
    ]
    engine.run_until_idle()

    for i, r in enumerate(reqs):
        print(f"req{i} ({r.finish_reason}): {r.out_tokens}")
    per_round = engine.spec_emitted / max(engine.spec_rounds, 1)
    print(f"speculative: {engine.spec_rounds} verify rounds, "
          f"{per_round:.2f} tokens/round")
    print(f"prefix cache: {engine.pages.prefix_hits} full-page hits, "
          f"{engine.pages.prefix_partial_hits} sub-page copies "
          f"({engine.pages.prefix_tokens_reused} tokens reused)")


if __name__ == "__main__":
    main()
