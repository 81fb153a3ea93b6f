"""Incremental multi-turn chat — the KV cache persists across turns so
each turn prefills only the new tokens (the reference's llm-chat
re-prefills the whole history every turn), with optional attention-sink
streaming for unbounded conversations.

    python examples/chat_session.py
"""

import jax

from bigdl_tpu import ChatSession
from bigdl_tpu.api import TpuModel, optimize_model
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import PRESETS


def main():
    cfg = PRESETS["tiny-llama"]
    params = optimize_model(llama.init_params(cfg, jax.random.PRNGKey(7)), cfg)
    model = TpuModel(cfg, params, "sym_int4")

    sess = ChatSession(model, max_len=256)
    turns = [[3, 1, 4, 1, 5, 9], [2, 7, 1, 8], [11, 12, 13]]
    history = []
    for t in turns:
        reply = sess.send(t, max_new_tokens=8)
        history += t + reply
        print(f"turn ({len(t)} new tokens, cache pos {sess.pos}):", reply)

    # incremental == one-shot on the full transcript
    full = model.generate([history[: -8] ], max_new_tokens=8)[0].tolist()
    assert reply == full
    print("incremental replies match full-history generate")

    # unbounded conversation in a fixed 48-slot window
    stream = ChatSession(model, streaming=(4, 48))
    for i in range(8):
        stream.send([5 + i, 6, 7], max_new_tokens=8)
    print(f"8 turns through a 48-slot sink window; cache pos {stream.pos}")


if __name__ == "__main__":
    main()
