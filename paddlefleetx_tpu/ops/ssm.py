"""The state-space (Mamba-2) decode step's state update, in place.

A row of a served state-space layer keeps ONE recurrent state ``S``
[heads, head_dim, state] whatever its length, and a decode step is the
recurrence once:

    S = exp(dt A) S + (dt x) (x) B          y = S C + D x

per (slot, head); ``B`` and ``C`` [groups, state] are shared by the heads of
a group.  The work is the state itself: 2 x heads x head_dim x state x 4
bytes a row and layer read and written for 5 FLOPs an element, so the
kernel is bound by the HBM and a copy of the states must never be made:
all the layers' states live in ONE array that the serving step carries
through its layer loop, and the kernel rewrites one layer's slots through
``input_output_aliases`` (models/gpt/generation.py, docs/nemotron_h.md).
For the same reason the kernel visits the LIVE slots only: its grid's first
bound is the number of live slots of the step and every block address reads
the slot from a compacted list (``ops/decode_attention.py`` ``live_slots``,
the same one the paged attention kernels follow), so a dead slot's state
costs no traffic and is left as it lies, bit for bit.

**Layout.**  The array is ``[layers, slots, R, state, W]``: the (head,
head_dim) pairs of a slot flattened and cut into ``R`` lane groups of
``W`` = 128, the state dim on the sublanes: ``states[l, s, r, n, w] = S[h,
p, n]`` with ``h * head_dim + p = r * W + w`` (:func:`pack_state`).  With
the state dim minor instead, ``y = S C`` is a reduction ALONG the lanes of
every vector register and ``dt x`` has to be spread across them; this way
``x``, ``dt`` and ``D`` enter and ``y`` leaves as lane rows, the layout XLA
hands them over in, the sum over the state is adds between registers, and
only ``B`` and ``C`` are spread, as columns.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddlefleetx_tpu.ops.decode_attention import live_slots  # the one list every kernel follows
from paddlefleetx_tpu.utils import device as _device

# bytes of state one grid step holds: 1 MB in and 1 MB out, twice for the
# pipeline, of the 16 MB a kernel may use (16 lane groups of [128, 128]
# float32; 8 of [256, 128])
_STEP_BYTES = 2 ** 20


def _groups_per_step(r: int, group_bytes: int) -> int:
    """The largest divisor of ``r`` lane groups, of ``group_bytes`` each
    ([state, W] in the states' dtype), that a grid step may hold."""
    most = max(1, _STEP_BYTES // group_bytes)
    return max(d for d in range(1, min(r, most) + 1) if r % d == 0)


def lane_width(heads: int, head_dim: int) -> int:
    """W: 128 lanes, or all (head, head_dim) pairs of a toy size."""
    pairs = heads * head_dim
    return 128 if pairs % 128 == 0 else pairs


def packed_shape(heads: int, head_dim: int, state: int) -> Tuple[int, int, int]:
    """[R, state, W] of one slot's state in one layer."""
    w = lane_width(heads, head_dim)
    return (heads * head_dim // w, state, w)


def pack_state(s: jax.Array) -> jax.Array:
    """S [..., heads, head_dim, state] -> [..., R, state, W]."""
    *lead, h, p, n = s.shape
    r, _, w = packed_shape(h, p, n)
    return jnp.swapaxes(s.reshape(*lead, r, w, n), -1, -2)


def unpack_state(packed: jax.Array, heads: int, head_dim: int) -> jax.Array:
    """[..., R, state, W] -> S [..., heads, head_dim, state]."""
    *lead, r, n, w = packed.shape
    return jnp.swapaxes(packed, -1, -2).reshape(*lead, heads, head_dim, n)


def _decode_kernel(layer_ref, live_ref, xdt_ref, dec_ref, dx_ref, bt_ref, ct_ref, s_ref, y_ref,
                   out_ref, *, rb):
    """One (live slot, block of ``rb`` lane groups) grid step.  ``s_ref`` / ``out_ref``
    [1, rb, state, W]: the same HBM pages (aliased); the small operands
    [1, 1, rb, W] rows and [1, 1, state, rb] columns."""
    del layer_ref, live_ref  # consumed by the index maps
    for k in range(rb):
        row = slice(k, k + 1)
        s = s_ref[0, k].astype(jnp.float32)  # [state, W]
        s = s * dec_ref[0, 0, row, :] + bt_ref[0, 0, :, row] * xdt_ref[0, 0, row, :]
        out_ref[0, k] = s.astype(out_ref.dtype)
        y_ref[0, 0, row, :] = (
            jnp.sum(s * ct_ref[0, 0, :, row], axis=0, keepdims=True) + dx_ref[0, 0, row, :])


def _lane_rows(v: jax.Array, nblk: int, rb: int, w: int) -> jax.Array:
    """[slots, heads * head_dim] -> [slots, nblk, rb, W]."""
    return v.reshape(v.shape[0], nblk, rb, w)


def _decode_pallas(states, layer, live, xdt, dec, dx, b_group, c_group, heads, head_dim):
    from jax.experimental.pallas import tpu as pltpu

    _, slots, r, n, w = states.shape
    groups = b_group.shape[1]
    per_group = heads // groups  # heads that share B and C
    span = max(1, w // head_dim)  # heads inside one lane group
    if per_group % span:
        raise ValueError(
            f"pfx_ssm_decode: a lane group of {w} holds {span} heads of {head_dim}, which "
            f"{per_group} heads a B/C group do not fill evenly; use impl='lax'")
    rb = _groups_per_step(r, n * w * states.dtype.itemsize)
    nblk = r // rb
    # the B/C group of each lane group, spread as the columns of a block
    of = (jax.lax.iota(jnp.int32, r) * w // head_dim) // per_group  # [R]

    def columns(v):  # [slots, groups, state] -> [slots, nblk, state, rb]
        return jnp.take(v.astype(jnp.float32), of, axis=1).reshape(
            slots, nblk, rb, n).swapaxes(-1, -2)

    # grid step i is the i-th LIVE slot: the grid's first bound is the live
    # count (no step, no traffic for a dead slot; none at all with no slot
    # live), and ``y`` of a slot that is not visited is never written
    live, count = live
    rows = pl.BlockSpec((1, 1, rb, w), lambda i, j, _, live_ref: (live_ref[i], j, 0, 0))
    cols = pl.BlockSpec((1, 1, n, rb), lambda i, j, _, live_ref: (live_ref[i], j, 0, 0))
    # the states enter WHOLE, all layers: the block address carries the
    # layer, and the other layers' pages are never touched
    page = pl.BlockSpec((None, 1, rb, n, w),
                        lambda i, j, layer_ref, live_ref: (layer_ref[0], live_ref[i], j, 0, 0))
    y, states = pl.pallas_call(
        functools.partial(_decode_kernel, rb=rb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(count[0], nblk),
            in_specs=[rows, rows, rows, cols, cols, page],
            out_specs=[rows, page]),
        out_shape=[jax.ShapeDtypeStruct((slots, nblk, rb, w), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={7: 1},
        interpret=_device.pallas_interpret(),
        name="pfx_ssm_decode",
    )(layer[None], live, _lane_rows(xdt, nblk, rb, w), _lane_rows(dec, nblk, rb, w),
      _lane_rows(dx, nblk, rb, w), columns(b_group), columns(c_group), states)
    return y.reshape(slots, heads, head_dim), states


def _write_kernel(slot_ref, new_ref, old_ref, out_ref):
    del slot_ref, old_ref  # the index maps address the slot; the page is overwritten whole
    out_ref[...] = new_ref[...].astype(out_ref.dtype)


def write_slot_states(states: jax.Array, new: jax.Array, slot, *, impl: str = "auto") -> jax.Array:
    """Overwrite batch slot ``slot`` of EVERY layer of ``states`` [layers,
    slots, R, state, W] with ``new`` [layers, R, state, W] (a prefill's
    states after its last real token), in place: the array that comes back
    IS the states.  The Pallas spelling (``pfx_ssm_write``) touches the
    slot's pages only; as an XLA update of a state that a transpose made,
    the compiler converted the WHOLE array to the update's layout and back
    (compiled for the v5e: 2.3 GB each way a prefill, more than fits)."""
    slot = jnp.asarray(slot, jnp.int32)
    use_pallas = impl == "pallas" or (impl == "auto" and not _device.pallas_interpret())
    if not use_pallas:
        return states.at[:, slot].set(new.astype(states.dtype))
    from jax.experimental.pallas import tpu as pltpu

    layers, _, r, n, w = states.shape
    rb = _groups_per_step(r, n * w * states.dtype.itemsize)
    page = pl.BlockSpec((None, None, rb, n, w), lambda l, j, slot_ref: (l, slot_ref[0], j, 0, 0))
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(layers, r // rb),
            in_specs=[pl.BlockSpec((None, rb, n, w), lambda l, j, *_: (l, j, 0, 0)), page],
            out_specs=page),
        out_shape=jax.ShapeDtypeStruct(states.shape, states.dtype),
        input_output_aliases={2: 0},
        interpret=_device.pallas_interpret(),
        name="pfx_ssm_write",
    )(slot[None], new, states)


def _decode_lax(states, layer, active, xdt, dec, dx, b_group, c_group, heads, head_dim):
    slots = states.shape[1]
    per_group = heads // b_group.shape[1]
    held = jax.lax.dynamic_index_in_dim(states, layer, keepdims=False)
    s = unpack_state(held, heads, head_dim).astype(jnp.float32)
    b_h = jnp.repeat(b_group.astype(jnp.float32), per_group, axis=1)  # [slots, heads, state]
    c_h = jnp.repeat(c_group.astype(jnp.float32), per_group, axis=1)
    pairs = (slots, heads, head_dim)
    s = s * dec.reshape(pairs)[..., None] + xdt.reshape(pairs)[..., None] * b_h[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", s, c_h, precision=jax.lax.Precision.HIGHEST)
    new = jnp.where(active[:, None, None, None], pack_state(s).astype(states.dtype), held)
    return y + dx.reshape(pairs), jax.lax.dynamic_update_index_in_dim(states, new, layer, axis=0)


def ssm_decode_update(states: jax.Array, x: jax.Array, dt: jax.Array, a: jax.Array,
                      b_group: jax.Array, c_group: jax.Array, d: jax.Array, *,
                      active: jax.Array, layer, live=None,
                      impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """One recurrence step of every LIVE slot of state-space layer ``layer``.

    ``states`` [layers, slots, R, state, W] (see the module's doc), rewritten
    in place: the array that comes back IS the states.  ``x`` [slots, heads,
    head_dim]; ``dt`` [slots, heads] >= 0, after its softplus; ``a`` [heads]
    < 0; ``b_group`` / ``c_group`` [slots, groups, state]; ``d`` [heads].
    ``active`` [slots] bool: a slot that is not active is not visited, its
    state stays as it is to the bit and its ``y`` is 0.  ``live``: what
    :func:`live_slots` makes of ``active``, from a caller that has it already.
    -> (y [slots, heads, head_dim] float32, states).  ``impl``: "auto"
    (Pallas ``pfx_ssm_decode`` on a TPU, ``jnp`` on the CPU) | "pallas" | "lax".
    """
    if impl not in ("auto", "pallas", "lax"):
        raise ValueError(f"ssm_decode_update impl {impl!r}; valid: auto, pallas, lax")
    slots, heads, head_dim = x.shape
    if states.ndim != 5 or states.shape[1] != slots or states.shape[2:] != packed_shape(
            heads, head_dim, b_group.shape[-1]):
        raise ValueError(f"states {states.shape} do not hold {slots} slots of "
                         f"{packed_shape(heads, head_dim, b_group.shape[-1])}")
    if active.shape != (slots,) or active.dtype != jnp.bool_:
        raise ValueError(f"active {active.dtype}{active.shape}: one bool a slot, [{slots}]")
    layer = jnp.asarray(layer, jnp.int32)
    xf, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    flat = (slots, heads * head_dim)
    xdt = (xf * dt[:, :, None]).reshape(flat)
    dec = jnp.broadcast_to(jnp.exp(dt * a.astype(jnp.float32))[:, :, None], x.shape).reshape(flat)
    dx = (xf * d.astype(jnp.float32)[None, :, None]).reshape(flat)
    operands = (xdt, dec, dx, b_group, c_group, heads, head_dim)
    if impl == "pallas" or (impl == "auto" and not _device.pallas_interpret()):
        y, states = _decode_pallas(
            states, layer, live_slots(active) if live is None else live, *operands)
    else:
        y, states = _decode_lax(states, layer, active, *operands)
    return jnp.where(active[:, None, None], y, 0.0), states
