"""What decides ``correct``: served tokens against the float32 reference.

After the window, a sample of the finished requests, drawn from the seed
and always holding the longest, is run once through the configuration's
plain reference (``bench/reference/<model_type>.py``) over prompt +
served tokens.  At each served position the reference's logits give the
gap by which the served token's logit lies below the reference's best.
Greedy decoding in bfloat16 flips only near-ties, a few per cent of
positions by a few hundredths; a wrong cache page, position or layer
leaves gaps the size of the logits' spread.

The number compared is the mean gap over the sample's served positions.
The widest gap was the first choice, but it does not separate the
bfloat16 engine from the int8 control by the factor of three a limit
needs on every cell (PERF.md §6 has both readings); the mean does.  The
mixes sample every request of a batch, so a fault confined to one slot
still adds its gaps to the mean.

The control (``gaps(..., control=True)``) puts the reference itself in
the program's place at int8: at each position of the same prompts and
tokens it reads the gap of the token the int8 reference ranks first.
``bench/control.py`` runs it; the benchmark's own runs do not.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import reference_module
from bench.weights import published


def sample(reqs: Sequence, k: int, rng: np.random.Generator) -> List:
    """``k`` finished requests: the longest (prompt + output) and ``k - 1``
    others drawn from ``rng``."""
    done = [r for r in reqs if not r.failed and r.out_tokens]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].prompt) + len(done[i].out_tokens))
    rest = [i for i in range(len(done)) if i != longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [done[longest]] + [done[rest[i]] for i in sorted(pick)]


def row(prompt: np.ndarray, served: Sequence[int], width: int
        ) -> Tuple[np.ndarray, np.ndarray]:
    """(tokens [width], positions [len(served)]): prompt + served tokens
    but the last, zero-padded; position ``plen - 1 + i`` predicts
    ``served[i]``."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    if len(seq) > width:
        raise ValueError(f"row of {len(seq)} tokens exceeds {width}")
    toks = np.zeros(width, np.int32)
    toks[:len(seq)] = seq
    return toks, len(prompt) - 1 + np.arange(len(served), dtype=np.int32)


class Reference:
    """Compiled reference for one configuration at one row width and one
    count of served tokens per request (both static: one program)."""

    def __init__(self, cfg: Dict, width: int, n_served: int):
        self.cfg = cfg
        self.width = width
        self.n = n_served
        mod = reference_module(cfg)

        def gaps(params, toks, at, served, quant):
            w = published(params, cfg)
            ref = mod.forward_logits(w, cfg, toks, at)
            best = jnp.max(ref, axis=-1)
            if quant is None:
                pick = served
            else:
                low = mod.forward_logits(w, cfg, toks, at, quant=quant)
                pick = jnp.argmax(low, axis=-1)
            return best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]

        self._program = jax.jit(lambda p, t, a, s: gaps(p, t, a, s, None))
        self._control = jax.jit(lambda p, t, a, s: gaps(p, t, a, s, "int8"))

    def gaps(self, params, reqs, control: bool = False) -> np.ndarray:
        """Per-position gaps over ``reqs``, request by request: of the
        served tokens, or with ``control`` of the int8 reference's first
        choice at the same positions of the same rows."""
        fn = self._control if control else self._program
        out = []
        for r in reqs:
            served = list(r.out_tokens)
            if len(served) != self.n:
                raise ValueError(f"request {r.rid} served {len(served)} of "
                                 f"{self.n} tokens")
            if max(served) >= self.cfg["vocab_size"] or min(served) < 0:
                raise ValueError(f"request {r.rid} served a token outside "
                                 "the vocabulary")
            toks, at = row(r.prompt, served, self.width)
            out.append(np.asarray(fn(params, jnp.asarray(toks),
                                     jnp.asarray(at),
                                     jnp.asarray(served, jnp.int32))))
        return np.concatenate(out) if out else np.zeros(0)

    def program_gap(self, params, reqs) -> float:
        """Mean gap of the served tokens over ``reqs``."""
        return float(self.gaps(params, reqs).mean())
