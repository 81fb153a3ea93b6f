"""Device time of the delta rule's CHUNKED form in an admission's prefill:
every operation of `engine_paged_prefill` whose name stack passes through the
scope `kda_prefill` (bigdl_tpu/kvhybrid.kda_chunked: XLA's, there is no
kernel), all KDA layers, summed an execution wholly inside the traced
seconds; the median over those executions, ms. The scope stands INSIDE the
vocabulary's `attn`, so the account of `bench/reduce/scopes.py` gives it to
that row; this reader walks the placed operations itself. The loops the form
makes (`lax.map` over the chunks, the walk over the state) stand under the
scope whole, so a fusion rooted anywhere inside them counts. None for a
program without the scope, or where the trace's metadata cannot be read."""

import os

from bench.reduce import scopes, xplane
from bench.stats import percentile

ENTRIES = ("engine",)
SCOPE, PROGRAM = "kda_prefill", "engine_paged_prefill"


def read(run):
    dev = run.device
    if dev is None:
        return None
    try:
        metadata = run.extra.get("scope_metadata")
        if metadata is None:  # read once; `scopes.account` finds it there
            metadata = run.extra["scope_metadata"] = scopes.read_metadata(
                xplane.find_trace(os.path.join(run.cell.root,
                                               ".bench_trace")))
        execs = scopes.executions(dev.loaded)
        whole = {i for i, (_, name, a, b, _) in enumerate(execs)
                 if PROGRAM in name and dev.begin <= a and b <= dev.end}
        per = {}
        for op in scopes.place(dev.loaded, metadata):
            if op.execution in whole and SCOPE in op.tf_op.split("/"):
                per[op.execution] = per.get(op.execution, 0.0) + op.self_s
    except Exception:  # a trace that cannot be read: `scopes.account` says why
        return None
    return percentile(list(per.values()), 50) * 1e3 if per else None
