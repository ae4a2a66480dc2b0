"""Mean host time of one grouped prefill call (`admit`: prefill, cache
insert and the host copy of its logits), over the calls that started in
the window; a harness span around each lane's `admit`."""


def read(rec):
    d = [s["t1"] - s["t0"] for s in rec.spans
         if s["name"] == "admit" and rec.in_window(s["t0"])]
    return 1e3 * sum(d) / len(d) if d else None
