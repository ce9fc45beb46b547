"""The one traffic generator: a mix is a data file of parameters beside this
file (``<mix>.json``), and this module turns it and a seed into token rows.

``markov_rows`` is ``adapcc_tpu/workloads/train_gpt2.markov_corpus`` +
``pack_sequences``, copied so that no later PR can change the yardstick, and
stepped for all rows at once: every packed row is its own walk of one sparse
random Markov chain (each token has ``branching`` likely successors, so the
entropy is about log(branching) and a language model has structure to
learn).  The original walks one stream token by token in Python, seconds per
million tokens; this one takes 1,024 vectorised steps whatever the row count.
Every seed gives the same shapes and the same amount of work.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

import numpy as np

_HERE = Path(__file__).resolve().parent


def load_mix(name: str, root: Path = _HERE) -> Dict[str, Any]:
    path = Path(root) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r}: {path} is missing")
    return json.loads(path.read_text())


def markov_rows(rows: int, seq_len: int, vocab_size: int, branching: int, seed: int) -> np.ndarray:
    """``[rows, seq_len]`` int32 tokens."""
    rng = np.random.default_rng(seed)
    successors = rng.integers(0, vocab_size, size=(vocab_size, branching))
    cum = rng.dirichlet(np.ones(branching) * 2.0, size=vocab_size).cumsum(axis=1)
    uniforms = rng.random((seq_len, rows))
    out = np.empty((seq_len, rows), dtype=np.int32)
    tok = rng.integers(0, vocab_size, size=rows)
    for t in range(seq_len):
        out[t] = tok
        # inverse transform: how many cumulative bounds the uniform has passed
        pick = (uniforms[t][:, None] >= cum[tok]).sum(axis=1).clip(max=branching - 1)
        tok = successors[tok, pick]
    return np.ascontiguousarray(out.T)


def make_rows(mix: Dict[str, Any], vocab_size: int, seed: int) -> np.ndarray:
    """The cell's corpus: ``mix["corpus_rows"]`` packed rows of
    ``mix["seq_len"]`` tokens."""
    return markov_rows(
        int(mix["corpus_rows"]), int(mix["seq_len"]), int(vocab_size),
        int(mix["branching"]), int(seed),
    )
