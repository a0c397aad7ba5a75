"""Mean time from the engine's ``_emit`` of a chunk's newest tokens (on
the step thread) to ``resp.write`` of that chunk returning (on the loop
thread): the hop to the loop, ``Backend.generate``'s token loop, the
detokeniser pool and back, the delta generator, ``json.dumps``, the
write. Summed by the frontend for every content chunk
(``emit_to_wire_seconds_total`` / ``emit_to_wire_total``)."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "emit_to_wire_seconds_total",
                          "emit_to_wire_total", 1000.0)
