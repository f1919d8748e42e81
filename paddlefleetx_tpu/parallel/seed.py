"""Seed / PRNG-key discipline.

The reference maintains three seed streams (``ppfleetx/distributed/apis/
env.py:34-98``): a parameter seed shared across dp/sharding ranks, a
``global_seed`` equal within an mp group (dropout on replicated activations)
and a ``local_seed`` unique per rank (dropout on sharded activations),
registered in Paddle's RNG-state tracker for TP determinism.

Under JAX+GSPMD the same guarantees come from key *derivation*, not rank
bookkeeping: programs are written against global arrays, so one seed yields
identical init regardless of the mesh layout — which is exactly the
reference's "precision validation across layouts" goal (env.py:62-71) — and
one logical mask per dropout site, so an mp group agrees on the mask of a
replicated activation.  The tracker below provides named, collision-free
streams:

    params    — model init (fold_in=0)
    global    — dropout applied to activations replicated across `model`
    local     — dropout applied to activations sharded across `model`
    data      — dataset shuffling / sampler seeds

Per-step keys fold in the step counter; per-layer keys fold in layer id.

Which generator draws the bits.  ``params`` and ``data`` are jax's default
(threefry) keys: seeded weights and the sampler's order are bit-identical on
every platform and under every layout.  ``global`` and ``local`` are ``rbg``
keys, always and on every platform: a draw from them is one
``lax.rng_bit_generator`` (the chip's bit generator on a TPU), and only the
scalar ``split`` / ``fold_in`` that derive a key per step, layer, site and
microbatch stay threefry.  A threefry mask is 20 rounds of integer
arithmetic per word that XLA:TPU copies into whatever consumes the mask: in
the 345M step eight fusions of the layer loop, the epilogue of the MLP's
``fc_out`` product among them, half of the step's device time (PERF.md
section 6, PR 32).  What ``rbg`` gives
up: a mask's bits are not promised equal across platforms, jax versions or
shardings, nor under ``vmap`` over keys (nothing here maps a dropout key);
within one compiled program a key replays its bits, which is what
recompute and resume need (``tests/test_dropout_streams.py`` holds rate,
independence and replay).  Under a multi-device mesh the partitioner does
not split the instruction: every device draws the whole logical mask and
keeps its shard.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax

_STREAM_IDS = {"params": 0, "global": 1, "local": 2, "data": 3}
# the streams whose keys draw whole activation-sized masks on the device
_STREAM_IMPL = {"global": "rbg", "local": "rbg"}


class SeedTracker:
    """Named PRNG streams derived from one root seed (see the module
    docstring for which generator each stream uses and why)."""

    def __init__(self, seed: int):
        self.seed = int(seed)
        self._streams: Dict[str, jax.Array] = {
            name: jax.random.fold_in(
                jax.random.key(self.seed, impl=_STREAM_IMPL.get(name)), sid
            )
            for name, sid in _STREAM_IDS.items()
        }

    def key(self, stream: str, *folds: int) -> jax.Array:
        """Key for ``stream`` with optional (step, layer, ...) folds."""
        k = self._streams[stream]
        for f in folds:
            k = jax.random.fold_in(k, f)
        return k

    def params_key(self) -> jax.Array:
        return self.key("params")

    def dropout_key(self, step: int) -> jax.Array:
        return self.key("global", step)

    def data_seed(self) -> int:
        # int seed for host-side numpy RNGs (sampler shuffling)
        return int(jax.random.randint(self.key("data"), (), 0, 2**31 - 1))


_TRACKER: Optional[SeedTracker] = None


def init_seed(seed: int) -> SeedTracker:
    global _TRACKER
    _TRACKER = SeedTracker(seed)
    return _TRACKER


def get_seed_tracker() -> SeedTracker:
    if _TRACKER is None:
        raise RuntimeError("seed tracker not initialised; call init_seed first")
    return _TRACKER
