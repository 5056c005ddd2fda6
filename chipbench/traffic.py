"""Traffic generators, driven by the parameters of a traffic file.

* ``open_loop``: a serving schedule of Poisson arrivals with lognormal
  prompt and output lengths, ``rate x seconds`` requests due inside the
  window.  Their sizes and arrival times are drawn from the traffic
  file's ``shape_seed`` (the gaps scaled to fill the window), so every
  run offers the same work at the same moments; a run's seed draws the
  token ids.  (At 0.8 of the knee the order of the requests alone moved
  the tail of time to first token by a third from seed to seed, so the
  seed no longer permutes them.)
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


@dataclasses.dataclass(frozen=True)
class Request:
    due: float               # seconds after the window opens
    prompt: List[int]
    max_new: int


def _lognormal_int(rng, n, median, sigma, lo, hi) -> np.ndarray:
    x = np.exp(np.log(median) + sigma * rng.standard_normal(n))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def open_loop(seed: int, *, rate: float, seconds: float, shape_seed: int,
              prompt: dict, output: dict, vocab: int) -> List[Request]:
    """The requests due in a window of ``seconds``, in order."""
    n = max(1, int(round(rate * seconds)))
    fixed = _rng(shape_seed, 2)
    gaps = fixed.exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    plens = _lognormal_int(fixed, n, **prompt)
    olens = _lognormal_int(fixed, n, **output)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    rng = _rng(seed, 3)
    return [Request(float(due[i]),
                    rng.integers(1, vocab, int(plens[i])).tolist(),
                    int(olens[i]))
            for i in range(n)]


def warmup_prompts(seed: int, lengths, vocab: int) -> List[List[int]]:
    rng = _rng(seed, 4)
    return [rng.integers(1, vocab, int(n)).tolist() for n in lengths]
