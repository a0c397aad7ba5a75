"""Forwards a row went through for each token it emitted, for a model
that generates by diffusion over blocks: ``diffusion_forwards_total``
(denoising forwards in which the row had a masked position, plus the
commit forward of each block, counted by the window program itself a
row) over ``diffusion_tokens_total``, as deltas between the two
``stats()`` reads around the window. A block of 4 under a strategy that
makes one position final a forward costs 5 forwards for 4 tokens: 1.25
is the floor there; a prompt's tail (a first block with fewer new
positions) and tokens dropped at ``max_tokens`` inside a block raise it.
A program without the counters reports nothing."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "diffusion_forwards_total",
                          "diffusion_tokens_total")
