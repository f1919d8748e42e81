"""What the dropout masks are held to (CPU; the chip runs the same path).

The ``global`` / ``local`` streams of ``parallel/seed.SeedTracker`` are
``rbg`` keys: the mask's bits come from ``lax.rng_bit_generator``, the
scalar ``split`` / ``fold_in`` that derive a key per step, layer, site and
microbatch stay threefry.  Held here: the keep rate, the values, that masks
which must differ are uncorrelated (and a mask with itself at a lag), that
a (seed, step) replays, and that the ``params`` / ``data`` streams are the
keys they were before dropout's changed, bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.common import dropout
from paddlefleetx_tpu.parallel.seed import SeedTracker

N = 1 << 22
SHAPE = (4, 1024, 1024)
SIGMAS = 4.0


def _site_key(tracker, step=3, layer=0, site=0, microbatch=None):
    """The key of one dropout site as ``models/gpt/model.py`` derives it
    from the engine's step key: split into (embedding, layers), the layer's
    index folded in (and, on the pipeline paths, the microbatch's), split
    into the block's two sites."""
    _, k_layers = jax.random.split(tracker.dropout_key(step))
    k = jax.random.fold_in(k_layers, layer)
    if microbatch is not None:
        k = jax.random.fold_in(k, microbatch)
    return jax.random.split(k)[site]


def _mask(key, keep=0.9):
    return np.asarray(jax.random.bernoulli(key, keep, SHAPE)).ravel()


def _correlation(a, b, keep=0.9):
    a = a.astype(np.float64) - keep
    b = b.astype(np.float64) - keep
    return float(np.mean(a * b) / (keep * (1.0 - keep)))


@pytest.mark.parametrize("stream", ["global", "local"])
def test_dropout_streams_are_hardware_generator_keys(stream):
    key = SeedTracker(1234).key(stream, 7)
    assert "rbg" in str(jax.random.key_impl(key))
    # and a traced draw from it is ONE rng_bit_generator, no threefry chain
    text = jax.jit(lambda k: jax.random.bernoulli(k, 0.9, (8, 128))).lower(key).as_text()
    assert "rng_bit_generator" in text
    assert "xor" not in text


@pytest.mark.parametrize("seed,params,data,data_seed", [
    (1024, [3064821049, 1980934005], [1168437924, 2376307975], 1700697103),
    (1234, [1264997412, 2518116175], [3512017511, 140093922], 1306401277),
    (2**31 - 1, [3894554595, 3657610310], [911888559, 1412594465], 1457741686),
])
def test_params_and_data_streams_are_the_keys_they_were(seed, params, data, data_seed):
    """Pinned from the tree before dropout's streams changed: seeded
    weights, the references' weights and the sampler's order do not move."""
    t = SeedTracker(seed)
    assert np.asarray(jax.random.key_data(t.params_key())).tolist() == params
    assert np.asarray(jax.random.key_data(t.key("data"))).tolist() == data
    assert t.data_seed() == data_seed
    root = jax.random.key(seed)
    np.testing.assert_array_equal(
        jax.random.key_data(t.params_key()),
        jax.random.key_data(jax.random.fold_in(root, 0)))


@pytest.mark.parametrize("rate", [0.1, 0.3])
def test_keep_rate_within_four_sigma(rate):
    keep = 1.0 - rate
    kept = int(_mask(_site_key(SeedTracker(1234)), keep).sum())
    sigma = np.sqrt(N * keep * rate)
    assert abs(kept - N * keep) <= SIGMAS * sigma, (kept, N * keep, sigma)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rate", [0.1, 0.3])
def test_kept_values_are_scaled_exactly_and_dropped_are_zero(rate, dtype):
    keep = 1.0 - rate
    key = _site_key(SeedTracker(1234), site=1)
    x = jax.random.normal(jax.random.key(0), SHAPE, dtype)
    x = jnp.where(x == 0, jnp.ones_like(x), x)  # so a zero in y is a dropped element
    out = dropout(key, x, rate, True)
    assert out.dtype == dtype
    y = np.asarray(out.astype(jnp.float32)).ravel()
    want = np.asarray((x / keep).astype(dtype).astype(jnp.float32)).ravel()
    mask = _mask(key, keep)
    np.testing.assert_array_equal(y[mask], want[mask])
    assert not y[~mask].any()
    assert np.all(want != 0)
    # and off: the input itself, the key untouched
    assert dropout(key, x, rate, False) is x
    assert dropout(key, x, 0.0, True) is x
    assert dropout(None, x, rate, True) is x


@pytest.mark.parametrize("other", [
    pytest.param(dict(site=1), id="two-sites"),
    pytest.param(dict(layer=1), id="two-layers"),
    pytest.param(dict(step=4), id="two-steps"),
    pytest.param(dict(microbatch=1), id="two-microbatches"),
])
def test_masks_that_must_differ_are_uncorrelated(other):
    t = SeedTracker(1234)
    base = dict(microbatch=0) if "microbatch" in other else {}
    a, b = _mask(_site_key(t, **base)), _mask(_site_key(t, **{**base, **other}))
    assert abs(_correlation(a, b)) <= SIGMAS / np.sqrt(N)


def test_embedding_and_layer_masks_are_uncorrelated():
    t = SeedTracker(1234)
    k_embed, _ = jax.random.split(t.dropout_key(3))
    assert abs(_correlation(_mask(k_embed), _mask(_site_key(t)))) <= SIGMAS / np.sqrt(N)


def test_global_and_local_streams_are_uncorrelated():
    t = SeedTracker(1234)
    a, b = _mask(t.key("global", 3)), _mask(t.key("local", 3))
    assert abs(_correlation(a, b)) <= SIGMAS / np.sqrt(N)


@pytest.mark.parametrize("lag", [1, 128, 1024])
def test_a_mask_is_uncorrelated_with_itself_at_a_lag(lag):
    m = _mask(_site_key(SeedTracker(1234)))
    assert abs(_correlation(m[:-lag], m[lag:])) <= SIGMAS / np.sqrt(N - lag)


def test_two_seeds_give_uncorrelated_masks():
    a, b = _mask(_site_key(SeedTracker(1234))), _mask(_site_key(SeedTracker(1235)))
    assert abs(_correlation(a, b)) <= SIGMAS / np.sqrt(N)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_same_seed_and_step_replay_the_mask(jit):
    """Twice from one tracker, and from a tracker rebuilt from the seed (a
    resumed run): the same key data and the same bits."""
    draw = (lambda k: jax.random.bernoulli(k, 0.9, SHAPE))
    if jit:
        draw = jax.jit(draw)
    t = SeedTracker(1234)
    first = np.asarray(draw(_site_key(t, step=11)))
    np.testing.assert_array_equal(first, np.asarray(draw(_site_key(t, step=11))))
    again = SeedTracker(1234)
    np.testing.assert_array_equal(
        jax.random.key_data(t.dropout_key(11)), jax.random.key_data(again.dropout_key(11)))
    np.testing.assert_array_equal(first, np.asarray(draw(_site_key(again, step=11))))
    assert (first != np.asarray(draw(_site_key(again, step=12)))).any()


def test_step_folded_inside_a_program_is_the_step_folded_outside():
    """The engine folds a TRACED step counter into the stream's key inside
    the jitted step; ``dropout_key(step)`` folds a Python int outside."""
    t = SeedTracker(1234)
    inside = jax.jit(lambda s: jax.random.key_data(jax.random.fold_in(t.key("global"), s)))
    np.testing.assert_array_equal(
        inside(jnp.int32(9)), jax.random.key_data(t.dropout_key(9)))
