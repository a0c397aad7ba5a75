"""The decode kernel's events (``paged_attention_decode_layered``, called
by a block window with a block's L queries folded into its group axis)
as a share of the time an operation ran on the device, in the traced
slice: ``paged_attn_busy_share`` for a configuration that generates by
diffusion over blocks (``block_length`` in its config.json); any other
reports nothing."""


def read(raw):
    t = raw["trace"]
    if not raw["model"]["config"].get("block_length"):
        return None
    if not t or t["busy_s"] <= 0 or t["kernel_s"] <= 0:
        return None
    return 100.0 * t["kernel_s"] / t["busy_s"]
