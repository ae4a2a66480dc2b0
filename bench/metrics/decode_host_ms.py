"""Host time per decode round in which the chip has nothing of the
round to do, from the program's own spans (on in the traced run): the
`decode.dispatch` (build the token and position arrays, launch the
step) and `decode.sample` (finite check and argmax) spans, summed over
the window and divided by its `decode_round` spans.  The round's
`decode.fetch` (waiting for the device, then the copy) is left out."""

HOST = ("decode.dispatch", "decode.sample")


def read(rec):
    inside = [s for s in rec.engine_spans if rec.in_window(s["t0"])]
    host = [s["dur"] for s in inside if s["name"] in HOST]
    rounds = sum(1 for s in inside if s["name"] == "decode_round")
    return 1e3 * sum(host) / rounds if host and rounds else None
