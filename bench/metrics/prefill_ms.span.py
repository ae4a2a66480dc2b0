"""Mean duration of one grouped prefill call from the program's own
`admit` span (on in the traced run: host launch, prefill, cache insert,
the host copy of its logits and the first token's sampling), over the
calls that started in the window.  The in-program twin of the harness's
`prefill_ms.batch`."""


def read(rec):
    d = [s["dur"] for s in rec.engine_spans
         if s["name"] == "admit" and rec.in_window(s["t0"])]
    return 1e3 * sum(d) / len(d) if d else None
