"""``latent_attn_busy_share`` for ``kimi-linear-48b-a3b.doc-reason``: the
accepted reader itself (device time under the scope ``attn.latent``,
which models/kimi_linear.py's attending layers keep from models/mla.py,
over busy time), under a name of its own because the accepted entry's
list is pinned to its one cell by tests/benchmark/test_bm_kanana.py,
which a ``model_config`` PR may not edit. Two layers of eight attend
here."""

import os

from benchmark.harness import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(raw):
    return cells.load_reader("latent_attn_busy_share", ROOT)(raw)
