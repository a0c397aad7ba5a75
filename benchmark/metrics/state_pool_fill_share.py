"""How much of the recurrent-state pool the traffic occupied: slots held
by admitted sequences (from admission to the release of the row, a
finished row's wait for the windows in flight included) over the slots
there are, both summed by the engine at every decode dispatch between
the two ``stats()`` reads around the window (``state_slots_held_total`` /
``state_slots_seen_total``): idle time before the window opens and while
it drains counts nothing. A program without a state pool reports
nothing."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "state_slots_held_total",
                          "state_slots_seen_total", 100.0)
