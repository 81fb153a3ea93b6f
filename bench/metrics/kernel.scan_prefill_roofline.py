"""Share of its roofline the kernel `mamba1_prefill` reaches in an
admission's prefill, in %: the least time the chip could take for the
tokens' x, dt, B, C in and y out and a row's state once each way a call
(bench/costs_scan.py; the larger of bytes over peak bandwidth and FLOPs over
peak FLOP/s), with the tokens from the `scan_tokens` argument of the traced
`prefill` spans, over the device time of the `mamba1_prefill` events inside
`engine_paged_prefill`, both as means over the traced seconds' prefills.

It reads LOW, and should: the kernel's work is `exp` and multiply-adds on
the VPU and the EUP, 6 a state element and token, 16 x 5120 of them a token
and layer, and `peaks.json` has the MXU's peak only (197 TFLOP/s, which
nothing here can reach). Against the table the BYTES bound it (80 KB a token
and layer against 0.5 MFLOP: 100 ns against 2.5 at the MXU's peak), so the
share says how far from a bandwidth-bound scan the kernel stands. A VPU peak
in the table is a `benchmark` issue's (PERF.md section 7). A bucket's padded
tokens are neither counted nor, past a block, computed, so the share cannot
read over 100%. None where the configuration lacks the keys, the spans the
argument or the trace the kernel."""

from bench import costs, costs_scan

ENTRIES = ("engine",)


def read(run):
    dev = run.device
    if dev is None or not costs_scan.knows(run.hf):
        return None
    n_calls, secs = dev.kernel_in_program("mamba1_prefill",
                                          "engine_paged_prefill")
    lo, hi = dev.begin + dev.offset, dev.end + dev.offset
    spans = [(t, a) for t, _, a in run.span_list("prefill")
             if a.get("scan_tokens")]
    inside = [a for t, a in spans if lo <= t < hi] or [a for _, a in spans]
    if not n_calls or not secs or not inside:
        return None
    tokens = sum(a["scan_tokens"] for a in inside) / len(inside)
    least = costs.roofline_seconds(
        costs_scan.prefill_cost(run.hf, tokens), run.peak)[0]
    return 100.0 * least / (secs / n_calls)
