"""The comparison that decides ``correct`` for a served model.

After the window, a sample of the requests the run served, drawn from the
seed and always holding the longest one, is run through the float32
reference once, over each prompt followed by its served tokens.  Finished
requests come first; a cell above the knee, whose long outputs finish after
the window, adds requests still decoding, whose tokens served so far are as
final as a finished request's.  At every
position that produced a served token, the gap is the reference's best logit
minus the reference's logit of the served token.  Greedy decoding serves the
program's own best token, so the gap measures how far the program's
arithmetic strayed; the widest gap over the sample is compared with the
configuration's limit.  The control reads the same gap for the token that a
float8 reference puts first (``reference.score(quant=True)``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import numpy as np

from bench import reference

MIN_SERVED_TOKENS = 400    # "some hundreds of served tokens"
MAX_REQUESTS = 8


@dataclass
class Served:
    prompt: List[int]
    output: List[int]


def sample(finished: Sequence[Served], decoding: Sequence[Served],
           seed: int) -> List[Served]:
    """The longest request, then others in a seeded order, finished ones
    first, until the sample holds ``MIN_SERVED_TOKENS`` served tokens."""
    pool = list(finished) + [r for r in decoding if r.output]
    if not pool:
        return []
    longest = max(range(len(pool)),
                  key=lambda i: len(pool[i].prompt) + len(pool[i].output))
    rng = np.random.default_rng(seed + 1)
    rest = [i for i in rng.permutation(len(finished)) if i != longest]
    rest += [i for i in len(finished) + rng.permutation(len(pool) - len(finished))
             if i != longest]
    picks, n = [longest], len(pool[longest].output)
    for i in rest:
        if n >= MIN_SERVED_TOKENS or len(picks) >= MAX_REQUESTS:
            break
        picks.append(int(i))
        n += len(pool[int(i)].output)
    return [pool[i] for i in picks]


def _inputs(reqs: Sequence[Served]):
    seqs = [r.prompt + r.output[:-1] for r in reqs]
    starts = [len(r.prompt) - 1 for r in reqs]
    served = [np.asarray(r.output, np.int32)[:, None] for r in reqs]
    return seqs, starts, served


def served_gap(d: dict, seed: int, reqs: Sequence[Served]) -> float:
    """Widest gap of a served token below the reference's best logit."""
    seqs, starts, served = _inputs(reqs)
    out = reference.score(d, seed, seqs, starts, served)
    return float(max((best - cl[:, 0]).max() for best, _, cl in out))


def control_gaps(d: dict, seed: int, reqs: Sequence[Served]):
    """(served gap, control gap) over the same sample: the control is the
    float8 reference's first choice at each served position, judged by the
    float32 reference."""
    seqs, starts, served = _inputs(reqs)
    low = reference.score(d, seed, seqs, starts, served, quant=True)
    both = [np.concatenate([s, arg[:, None]], 1)
            for s, (_, arg, _) in zip(served, low)]
    out = reference.score(d, seed, seqs, starts, both)
    served_g = max((best - cl[:, 0]).max() for best, _, cl in out)
    control_g = max((best - cl[:, 1]).max() for best, _, cl in out)
    return float(served_g), float(control_g)
