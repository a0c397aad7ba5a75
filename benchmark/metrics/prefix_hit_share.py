"""Share of prompt tokens the page manager served from cached pages:
prefix_hit_tokens_total over prompt_tokens_total, as deltas over the
window."""


def read(raw):
    s0, s1 = raw["stats0"], raw["stats1"]
    prompt = s1["prompt_tokens_total"] - s0["prompt_tokens_total"]
    if prompt <= 0:
        return None
    hit = s1["prefix_hit_tokens_total"] - s0["prefix_hit_tokens_total"]
    return 100.0 * hit / prompt
