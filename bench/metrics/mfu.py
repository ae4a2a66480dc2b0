"""Model FLOPs of the tokens processed in the traced window, over the
window and the chip's int8 peak, in percent.  Prefills count at their
true prompt lengths, decode tokens at their true context (idle slots
and padding do not count); weights, attention and the head's one row
per token.  Every tier forms 8-bit products, so int8 is the peak."""

from bench import work


def read(rec):
    if rec.trace is None:
        return None
    cfg = rec.config
    flops = sum(work.prefill_flops(cfg, n)
                for s in rec.spans_traced("admit") for n in s["lens"])
    flops += sum(work.decode_flops(cfg, c)
                 for s in rec.spans_traced("decode_round")
                 for c in s["contexts"])
    peak = work.peaks(rec.device["kind"])["int8_ops"]
    return 100.0 * flops / rec.trace.window_s / peak
