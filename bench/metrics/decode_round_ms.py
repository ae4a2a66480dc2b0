"""Mean duration of one lane's decode round, from the engine's own
`decode_round` telemetry span (on in the traced run), over the rounds
that started in the window."""


def read(rec):
    d = [s["dur"] for s in rec.engine_spans
         if s["name"] == "decode_round" and rec.in_window(s["t0"])]
    return 1e3 * sum(d) / len(d) if d else None
