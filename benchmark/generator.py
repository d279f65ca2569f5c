"""The one traffic generator. A traffic mix is a data file,
``traffic/<name>.json``; this reads its parameters and yields the host
batches of a training job, every one fresh from the seed.

``kind``:

* ``mlm`` - BERT's published data rule (google-research/bert,
  ``create_pretraining_data.py``): every sequence gets exactly
  ``min(max_predictions_per_seq, round(masked_lm_prob * seq))`` targets,
  at positions drawn without replacement; the token there is replaced by
  ``mask_token_id``. A batch is ``(tokens, targets)`` with targets -1
  elsewhere. The count is fixed, so a head that runs on
  ``max_predictions_per_seq`` positions never drops a target.
* ``lm`` - uniform tokens; the next token is the target. A batch is
  ``tokens``.

Tokens are uniform over ``[1, vocab_size)``; every seed gives the same
sizes, in other values.
"""

from __future__ import annotations

import json
import os
from typing import Iterator

import numpy as np

KINDS = ("mlm", "lm")


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}")
    for key in ("batch_per_chip", "seq"):
        if not isinstance(mix.get(key), int) or mix[key] < 1:
            raise ValueError(f"{path}: {key} must be a positive integer")
    return mix


def find(name: str, dirs) -> str:
    for d in dirs:
        path = os.path.join(d, "traffic", name + ".json")
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no traffic/{name}.json under {list(dirs)}")


def targets_per_row(mix: dict) -> int:
    if mix["kind"] == "lm":
        return mix["seq"] - 1
    return min(mix["max_predictions_per_seq"],
               int(round(mix["masked_lm_prob"] * mix["seq"])))


def batches(mix: dict, vocab_size: int, chips: int, seed: int) -> Iterator:
    """Endless stream of global host batches, ``batch_per_chip * chips``
    rows each, a function of ``seed`` alone."""
    rng = np.random.default_rng(seed)
    rows, seq = mix["batch_per_chip"] * chips, mix["seq"]
    n = targets_per_row(mix)
    while True:
        tokens = rng.integers(1, vocab_size, size=(rows, seq),
                              dtype=np.int32)
        if mix["kind"] == "lm":
            yield tokens
            continue
        at = np.argpartition(rng.random((rows, seq)), n - 1, axis=1)[:, :n]
        targets = np.full((rows, seq), -1, np.int32)
        np.put_along_axis(targets, at, np.take_along_axis(tokens, at, 1), 1)
        np.put_along_axis(tokens, at, np.int32(mix["mask_token_id"]), 1)
        yield tokens, targets
