"""How much of the reserved KV pool the traffic occupied: pages held by
running sequences (the engine's ``kv_active_blocks``, a shared prefix
page counted once) over the pool's pages, mean of the samples taken once
a second through the window."""


def read(raw):
    samples = raw.get("pool_samples") or []
    if not samples:
        return None
    return 100.0 * sum(s["active"] / s["total"] for s in samples) \
        / len(samples)
