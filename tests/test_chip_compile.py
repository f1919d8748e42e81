"""Ask the TPU's compiler, without the chip.

Every kernel and program on a default path is COMPILED here for a
described ``v5e:2x2`` topology (shapes only; nothing runs), at the real
GPT-345M (16 heads x 64) and GPT-1.3B (16 x 128) head sizes in bf16.
Interpret mode — what every other kernel test in this suite runs — cannot
see what Mosaic refuses: a dynamic slice it cannot prove aligned, a block
that breaks the (8, 128) tiling rule, a kernel GSPMD cannot partition, a
step that does not fit 16 GB.  A compile that passes is not a chip run
(``chip_smoke.py`` is), but one that fails here would have failed there.

``pallas_interpret`` is steered from the test (the code under test still
sees the CPU backend); the persistent compile cache is off around these
compiles (a TPU executable cannot be read back without a chip).
"""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from paddlefleetx_tpu.utils import device as device_mod

HEAD_DIMS = [
    pytest.param(64, id="345M"),
    pytest.param(128, id="1.3B"),
]
HEADS = 16
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu / unknown topology on this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {exc!r}")


@pytest.fixture(autouse=True)
def _compile_for_chip(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(device_mod, "pallas_interpret", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, tree):
    """ShapeDtypeStructs for a pytree of (shape, dtype) leaves / arrays /
    ShapeDtypeStructs, all placed with ``sharding``."""
    def leaf(x):
        if isinstance(x, tuple):
            return jax.ShapeDtypeStruct(x[0], x[1], sharding=sharding)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    return jax.tree.map(
        leaf, tree,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple),
    )


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# Training kernels: flash fwd + both bwd schedules, fused LayerNorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d", HEAD_DIMS)
@pytest.mark.parametrize("bwd", ["split", "fused"])
def test_flash_fwd_bwd_compiles(topo, d, bwd):
    from paddlefleetx_tpu.ops.flash_attention import _flash_bsnd

    q = _shapes(_one_chip(topo), ((2, 1024, HEADS, d), BF16))

    def loss(q, k, v):
        out = _flash_bsnd(q, k, v, float(d ** -0.5), (512, 512), bwd)
        return jnp.sum(out.astype(jnp.float32))

    c = _compile(jax.grad(loss, (0, 1, 2)), q, q, q)
    assert _has_kernel(c)
    text = c.as_text()
    sites = {name: f"flash_bwd_{name}" in text for name in ("dq", "dkv", "fused")}
    assert sites == {"dq": bwd == "split", "dkv": bwd == "split", "fused": bwd == "fused"}


def _kernel_calls(text):
    """flash kernel name -> the dims of its Mosaic call's results and operands
    in a compiled text (an instruction is named after its kernel, with what
    differentiation put around it)."""
    calls = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S*?(pfx_flash_(?:fwd|bwd_fused|bwd_dq|bwd_dkv)(?:_bsh)?)[_.\d]* = (.*?) custom-call\(", line)
        if m and 'custom_call_target="tpu_custom_call"' in line:
            operands = re.search(r"operand_layout_constraints=\{(.*?\})\}", line).group(1)
            calls.setdefault(m.group(1), []).extend(
                re.findall(r"\w+\[([\d,]+)\]", m.group(2) + " " + operands))
    return calls


@pytest.mark.parametrize("seq", [512, 1024, 2048, 4096])
def test_flash_in_the_model_s_layout_compiles(topo, seq):
    """Every point ``_operand_layout`` enters: forward and fused backward on
    [batch, seq, heads*64], through the public call, which reads the layout
    and the schedule from these shapes.  The new calls are in the program,
    the transposed path's are not, and nothing a Mosaic call takes or gives
    has a minor dimension of 1 or 64 (a lane-1 column or a 64-wide head,
    either padded to 128 lanes wherever it is kept)."""
    from paddlefleetx_tpu.ops.flash_attention import _operand_layout, flash_attention

    assert _operand_layout(seq, HEADS, 64, 0, 1, BF16) == "bsh"
    q = _shapes(_one_chip(topo), ((16 if seq == 1024 else 2, seq, HEADS, 64), BF16))  # 1,024: the 345M cell's

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v).astype(jnp.float32))

    calls = _kernel_calls(_compile(jax.grad(loss, (0, 1, 2)), q, q, q).as_text())
    assert sorted(calls) == ["pfx_flash_bwd_fused_bsh", "pfx_flash_fwd_bsh"]  # noqa: E10 — kernel names
    for name, shapes in calls.items():
        assert len(shapes) == (9 if "bwd" in name else 5), (name, shapes)  # results + operands
        assert all(int(dims.split(",")[-1]) % 128 == 0 for dims in shapes), (name, shapes)


@pytest.mark.parametrize("hidden", [pytest.param(1024, id="345M"),
                                    pytest.param(2048, id="1.3B")])
def test_fused_layernorm_fwd_bwd_compiles(topo, hidden):
    from paddlefleetx_tpu.ops.fused_layernorm import fused_layer_norm

    one = _one_chip(topo)
    x = _shapes(one, ((4, 1024, hidden), BF16))
    w = _shapes(one, ((hidden,), jnp.float32))

    def loss(x, res, scale, bias):
        return jnp.sum(fused_layer_norm(x, scale, bias, residual=res)
                       .astype(jnp.float32))

    c = _compile(jax.grad(loss, (0, 1, 2, 3)), x, x, w, w)
    assert _has_kernel(c)


# ---------------------------------------------------------------------------
# Serving kernels: contiguous decode (t=1, spec chunk, prefill-sized t),
# paged decode (t=1, spec chunk; the block sizes serve.py documents),
# both int8 spellings
# ---------------------------------------------------------------------------

# an allocation init_cache would make for prompt 960 + 64 new + draft_k 4:
# a multiple of 8 that is NOT a multiple of the 256 block (clamped tail)
CACHE_LEN = 1032
CACHE_LEN_INT8 = 1152


@pytest.mark.parametrize("d", HEAD_DIMS)
@pytest.mark.parametrize("t", [1, 5, 512])
def test_contiguous_decode_compiles(topo, d, t):
    from paddlefleetx_tpu.ops.decode_attention import decode_attention

    one = _one_chip(topo)
    q = _shapes(one, ((2, t, HEADS, d), BF16))
    kv = _shapes(one, ((2, HEADS, CACHE_LEN, d), BF16))
    pos = _shapes(one, ((), jnp.int32))
    vf = _shapes(one, ((2,), jnp.int32))
    c = _compile(
        lambda q, k, v, pos, vf: decode_attention(q, k, v, pos, kv_valid_from=vf),
        q, kv, kv, pos, vf,
    )
    assert _has_kernel(c)


@pytest.mark.parametrize("d", HEAD_DIMS)
@pytest.mark.parametrize("t", [1, 5])
def test_contiguous_decode_int8_compiles(topo, d, t):
    from paddlefleetx_tpu.ops.decode_attention import decode_attention

    one = _one_chip(topo)
    q = _shapes(one, ((2, t, HEADS, d), BF16))
    kv = _shapes(one, ((2, HEADS, CACHE_LEN_INT8, d), jnp.int8))
    sc = _shapes(one, ((2, HEADS, CACHE_LEN_INT8), jnp.float32))
    pos = _shapes(one, ((), jnp.int32))
    c = _compile(
        lambda q, k, v, pos, ks, vs: decode_attention(
            q, k, v, pos, k_scale=ks, v_scale=vs),
        q, kv, kv, pos, sc, sc,
    )
    assert _has_kernel(c)


@pytest.mark.parametrize("d", HEAD_DIMS)
@pytest.mark.parametrize("t", [1, 5])
@pytest.mark.parametrize("bs", [16, 32, 128])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_compiles(topo, d, t, bs, kv_dtype):
    from paddlefleetx_tpu.ops.decode_attention import paged_decode_attention

    one = _one_chip(topo)
    nb, rows = 512, 8
    quant = kv_dtype == "int8"
    q = _shapes(one, ((rows, t, HEADS, d), BF16))
    pool = _shapes(one, ((nb, HEADS, bs, d), jnp.int8 if quant else BF16))
    tables = _shapes(one, ((rows, 1024 // bs), jnp.int32))
    positions = _shapes(one, ((rows,), jnp.int32))
    if quant:
        sc = _shapes(one, ((nb, HEADS, bs), jnp.float32))
        c = _compile(
            lambda q, k, v, tb, ps, ks, vs: paged_decode_attention(
                q, k, v, tb, ps, k_scale=ks, v_scale=vs),
            q, pool, pool, tables, positions, sc, sc,
        )
    else:
        c = _compile(paged_decode_attention, q, pool, pool, tables, positions)
    assert _has_kernel(c)


@pytest.mark.parametrize("rows,t,width", [
    # serve-1.3b-docs as the benchmark runs it: 8 rows, 513 pool blocks
    # (the auto arena), table-width buckets 64 and 32
    pytest.param(8, 1, 64, id="docs-cell"),
    pytest.param(8, 1, 32, id="docs-cell-w32"),
    # --cb-batch 32; a table narrower than one page group
    pytest.param(32, 1, 64, id="cb32"),
    pytest.param(8, 1, 4, id="w4"),
    # a prefill chunk through the paged path (prefix reuse): one row, a
    # whole prompt bucket of queries, cut into query tiles by the kernel
    pytest.param(1, 1024, 64, id="chunk-1024"),
])
def test_paged_decode_compiles_at_serving_shapes(topo, rows, t, width):
    from paddlefleetx_tpu.ops.decode_attention import paged_decode_attention

    one = _one_chip(topo)
    q = _shapes(one, ((rows, t, HEADS, 128), BF16))
    pool = _shapes(one, ((513, HEADS, 16, 128), BF16))
    tables = _shapes(one, ((rows, width), jnp.int32))
    positions = _shapes(one, ((rows,), jnp.int32))
    c = _compile(paged_decode_attention, q, pool, pool, tables, positions)
    assert _has_kernel(c)


def test_misaligned_cache_is_refused_not_rerouted(topo):
    """On a TPU ``impl="auto"`` is the kernel or an error — never lax."""
    from paddlefleetx_tpu.ops.decode_attention import decode_attention

    one = _one_chip(topo)
    q = _shapes(one, ((2, 1, HEADS, 64), BF16))
    kv = _shapes(one, ((2, HEADS, 1028, 64), BF16))
    pos = _shapes(one, ((), jnp.int32))
    with pytest.raises(ValueError, match="multiples of 8"):
        _compile(decode_attention, q, kv, kv, pos)


# ---------------------------------------------------------------------------
# Whole serving programs at GPT-345M width and depth
# ---------------------------------------------------------------------------


def _gpt345m():
    from paddlefleetx_tpu.models.gpt.config import GPTConfig

    return GPTConfig(
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0, dtype="bfloat16"
    )  # the dataclass defaults ARE 345M: 24 x 1024, 16 heads, vocab 50304


def _param_shapes(cfg, sharding):
    from paddlefleetx_tpu.models.gpt import model as gpt

    return _shapes(
        sharding, jax.eval_shape(lambda k: gpt.init(cfg, k), jax.random.key(0))
    )


def test_generate_345m_compiles(topo):
    """``generate()`` — the coalesce scheduler, GenerationServer and
    tools/inference.py all end here: prefill (t = bucket) and decode
    (t = 1) through the contiguous kernel."""
    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig, generate

    cfg = _gpt345m()
    one = _one_chip(topo)
    params = _param_shapes(cfg, one)
    gen = GenerationConfig(max_dec_len=64, decode_strategy="greedy_search")
    ids = _shapes(one, ((2, 128), jnp.int32))
    lens = _shapes(one, ((2,), jnp.int32))
    c = _compile(
        lambda p, ids, lens: generate(p, ids, cfg, gen, prompt_lens=lens),
        params, ids, lens,
    )
    assert _has_kernel(c)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_prefill_and_step_345m_compile(topo, kv_dtype):
    """The continuous scheduler's two programs: prefill-on-admit
    (``paged_prefill``) and the decode step (``paged_forward_step``), at
    the library's page of 16 slots."""
    from paddlefleetx_tpu.models.gpt.generation import (
        init_paged_pools,
        paged_forward_step,
        paged_prefill,
    )

    cfg = _gpt345m()
    one = _one_chip(topo)
    params = _param_shapes(cfg, one)
    nb, bs, rows, P_ = 256, 16, 8, 128
    pools = _shapes(one, jax.eval_shape(
        lambda: init_paged_pools(cfg, nb, bs, kv_dtype=kv_dtype)))

    prompt = _shapes(one, ((1, P_), jnp.int32))
    plen = _shapes(one, ((), jnp.int32))
    row = _shapes(one, ((P_ // bs,), jnp.int32))
    c = _compile(
        lambda p, prompt, plen, pools, row: paged_prefill(
            p, prompt, plen, pools, row, cfg),
        params, prompt, plen, pools, row,
    )
    assert _has_kernel(c)

    toks = _shapes(one, ((rows,), jnp.int32))
    tables = _shapes(one, ((rows, 1024 // bs), jnp.int32))
    positions = _shapes(one, ((rows,), jnp.int32))
    active = _shapes(one, ((rows,), jnp.bool_))
    c = _compile(
        lambda p, toks, pools, tb, ps, act: paged_forward_step(
            p, toks, pools, tb, ps, act, cfg),
        params, toks, pools, tables, positions, active,
    )
    assert _has_kernel(c)


# ---------------------------------------------------------------------------
# Kernels under a mesh: Mosaic kernels cannot be partitioned by GSPMD, so
# under a ShardingCtx they run inside shard_map (parallel/sharding.shard_kernel)
# ---------------------------------------------------------------------------


def _mesh_ctx(topo, **degrees):
    from paddlefleetx_tpu.models.gpt.model import ShardingCtx
    from paddlefleetx_tpu.parallel.mesh import MeshConfig, build_mesh
    from paddlefleetx_tpu.parallel.sharding import make_rules

    mesh = build_mesh(MeshConfig(**degrees), topo.devices)
    return mesh, ShardingCtx(mesh, make_rules(mesh=mesh))


@pytest.mark.parametrize("degrees", [
    pytest.param({"dp_degree": 2, "mp_degree": 2}, id="dp2mp2"),
    pytest.param({"dp_degree": 4}, id="dp4"),
])
def test_sharded_flash_and_fused_ln_compile(topo, degrees):
    """One sharded flash call (+ fused LN) on the 2x2 mesh: fwd + bwd with
    batch over ``data`` and heads over ``model``.  Bare, the compiler says
    "Mosaic kernels cannot be automatically partitioned".  The norm's shape
    is one of ``_norm_schedule``'s table on either mesh (a shard holds 4 or
    2 of the 8 x 2,048 rows), so ``layer_norm`` takes the kernel."""
    from paddlefleetx_tpu.models.gpt.model import layer_norm
    from paddlefleetx_tpu.ops.attention import attention

    mesh, ctx = _mesh_ctx(topo, **degrees)
    act = NamedSharding(mesh, P(("data", "fsdp"), None, None))
    x = _shapes(act, ((8, 2048, 1024), BF16))
    w_qkv = _shapes(NamedSharding(mesh, P(None, None, "model", None)),
                    ((1024, 3, HEADS, 64), BF16))
    w_ln = _shapes(NamedSharding(mesh, P()), ((1024,), jnp.float32))

    def loss(x, w_qkv, scale, bias):
        y = layer_norm(x, scale, bias, ctx=ctx)
        qkv = jnp.einsum("bsh,htnd->bstnd", y, w_qkv)
        out = attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], impl="flash", ctx=ctx)
        return jnp.sum(out.astype(jnp.float32))

    c = _compile(jax.grad(loss, (0, 1, 2, 3)), x, w_qkv, w_ln, w_ln)
    text = c.as_text()
    assert "tpu_custom_call" in text
    assert "pfx_ln_fwd" in text and "pfx_ln_bwd" in text  # noqa: E10 — kernel names
    # q/k/v reach the kernel as they were computed (batch- and heads-
    # sharded): nothing gathers them in front of it
    assert "all-gather" not in text


# ---------------------------------------------------------------------------
# Whole train steps of the documented configs (Engine.abstract_init: no
# state is materialized, the jitted step is lowered on described devices)
# ---------------------------------------------------------------------------

_SINGLE_YAML = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "configs", "gpt", "pretrain_gpt_345M_single.yaml",
)


@pytest.mark.parametrize("degrees,batch", [
    pytest.param({"dp_degree": 2, "mp_degree": 2}, 16, id="dp2mp2"),
    pytest.param({"mp_degree": 4}, 8, id="mp4"),
])
def test_generate_on_a_mesh_runs_a_batched_prefill_s_norms_in_the_kernel(topo, degrees, batch):
    """Tensor-parallel serving (``generate(..., ctx=)``) at one of
    ``GenerationServer``'s buckets: 8 prompts of 512 a shard are 4,096 rows x
    1,024, one of ``_norm_schedule``'s eight, so the prefill's norms take the
    kernel, and ``generation.py`` hands them its ``ctx``: the kernel sits in
    ``shard_map`` (bare, the compiler refuses the program: "Mosaic kernels
    cannot be automatically partitioned").  The decode steps' norms (8 rows)
    stay the composite: the sites are the prefill's alone."""
    from paddlefleetx_tpu.models.gpt import model as gpt
    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig, generate
    from paddlefleetx_tpu.parallel.sharding import tree_logical_to_sharding

    cfg = _gpt345m()
    mesh, ctx = _mesh_ctx(topo, **degrees)
    shardings = tree_logical_to_sharding(gpt.gpt_logical_axes(cfg), mesh, ctx.rules)
    shapes = jax.eval_shape(lambda k: gpt.init(cfg, k), jax.random.key(0))
    params = jax.tree.map(lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
                          shapes, shardings)
    rows = NamedSharding(mesh, P(("data", "fsdp")))
    ids = _shapes(NamedSharding(mesh, P(("data", "fsdp"), None)), ((batch, 512), jnp.int32))
    lens = _shapes(rows, ((batch,), jnp.int32))
    gen = GenerationConfig(max_dec_len=64, decode_strategy="greedy_search")
    with mesh:
        c = _compile(
            lambda p, ids, lens: generate(p, ids, cfg, gen, prompt_lens=lens, ctx=ctx),
            params, ids, lens,
        )
    text = c.as_text()
    # ln_1 and ln_2 in the prefill's layer loop, final_ln behind it; forward only
    assert {k: len(v) for k, v in _ln_sites(text).items()} == {"fwd": 3}
    assert "bf16[4096,1024]" in text


def _compile_train_step(topo, n_devices, overrides=(), yaml=None):
    from paddlefleetx_tpu.core.engine import Engine
    from paddlefleetx_tpu.core.module import build_module
    from paddlefleetx_tpu.parallel.env import init_dist_env
    from paddlefleetx_tpu.utils.config import get_config

    cfg = get_config(yaml or _SINGLE_YAML, overrides=list(overrides), num_devices=n_devices)
    mesh = init_dist_env(cfg, devices=topo.devices[:n_devices])
    with mesh:
        engine = Engine(cfg, build_module(cfg), mesh, abstract_init=True)
        b = int(cfg.Global.global_batch_size)
        s = int(cfg.Data.Train.dataset.max_seq_len)
        batch = {
            name: jax.ShapeDtypeStruct((b, s), dt, sharding=engine.batch_spec)
            for name, dt in (("tokens", np.int64), ("labels", np.int64),
                             ("loss_mask", np.float32), ("position_ids", np.int64))
        }
        return engine._train_step.lower(engine.state, batch).compile()


@functools.lru_cache(maxsize=None)  # 15-60 s of compile, shared by the cases below
def _step_345m(topo):
    return _compile_train_step(topo, 1)


@functools.lru_cache(maxsize=None)
def _step_trinity(topo):
    """The benchmark cell ``train-trinity-mini-1of8`` as its yaml and its
    configuration file state it."""
    import json

    root = os.path.join(os.path.dirname(_SINGLE_YAML), "..", "..")
    bench = os.path.join(root, "pfx_bench")  # noqa: E10 — a directory, not a metric
    with open(os.path.join(bench, "configs", "trinity-mini.json")) as f:
        config = json.load(f)
    return _compile_train_step(
        topo, 1, [f"Model.{k}={v}" for k, v in config["model"].items()]
        + ["Global.global_batch_size=2", "Global.local_batch_size=2",
           "Global.micro_batch_size=2"], yaml=os.path.join(root, config["yaml"]))


def test_documented_single_chip_config_fits_one_chip(topo):
    """README's first command — ``tools/train.py -c
    configs/gpt/pretrain_gpt_345M_single.yaml`` AS COMMITTED (batch 16,
    flash attention) — compiles for one 16 GB chip; a step that does not
    fit is refused by the compiler with RESOURCE_EXHAUSTED."""
    assert _has_kernel(_step_345m(topo))


def test_trinity_mini_share_fits_one_chip(topo):
    """The benchmark cell ``train-trinity-mini-1of8`` as its yaml and its
    configuration file state it (2 x 8192 tokens, published widths, 16 of
    128 experts, full recompute): the real train step compiles for one 16 GB
    chip, with the flash kernels (window and full, 32/4 heads, whole
    8192-position K/V in VMEM) and the grouped products as Mosaic calls.
    The sorted-pair buffer follows the load (``moe.buffer_ladder``: 32,768
    or the worst case's 131,072 rows a layer): each expert layer's
    sorted path is a conditional with one computation a rung, in the
    forward pass, in its recompute and in the backward pass, and the
    compiler counts no more bytes than for the one worst-case buffer
    (15,246,225,408 at the parent of PR 41, 14.2 GiB of the 15.75 it may
    use): a rung's residuals stay inside its branch."""
    import re

    c = _step_trinity(topo)
    text = c.as_text()
    assert "pfx_flash_fwd" in text and "pfx_flash_bwd_dkv" in text  # noqa: E10 — kernel names
    assert "ragged-dot" in text  # XLA:TPU's grouped product, a Mosaic call too
    # the forward-only kernel is the serving prefill's: training needs the two
    # transposed products that XLA derives from its own
    assert "pfx_grouped_matmul" not in text  # noqa: E10 — a kernel's name
    branches = re.findall(r" conditional\(.*?branch_computations=\{([^}]*)\}", text)
    assert len(branches) == 4 * 3  # expert layers x (forward, recompute, backward)
    assert all(len(b.split(",")) == 2 for b in branches)  # a computation a rung
    for rows in (32768, 131072):
        assert f"bf16[{rows},2048]" in text
    m = c.memory_analysis()
    held = m.argument_size_in_bytes + m.temp_size_in_bytes + m.generated_code_size_in_bytes
    assert 8e9 < m.argument_size_in_bytes < 9e9  # 705.5 M x 12 bytes of state
    assert held <= 15_246_225_408, held


def _mask_draws(text):
    """Where a compiled step draws activation-sized random words (2**20 and
    more of u32): (``rng-bit-generator`` instructions, threefry rounds seen
    as ``xor`` on such a tensor) inside the program's loop bodies, the
    fusions nested in them included, and the same two counts over the
    whole program."""
    import re

    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    todo = list(set(re.findall(r"body=%?([\w.\-]+)", text)))
    assert todo, "the step has no layer loop"
    inside = set()
    while todo:
        name = todo.pop()
        if name in inside or name not in comps:
            continue
        inside.add(name)
        todo += re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                           "\n".join(comps[name]))

    def count(lines):
        rbg = xor = 0
        for line in lines:
            m = re.search(r"= u32\[([\d,]+)\]\S* (rng-bit-generator|xor)\(", line)
            if m and np.prod([int(d) for d in m.group(1).split(",")]) >= 2**20:
                rbg += m.group(2) == "rng-bit-generator"
                xor += m.group(2) == "xor"
        return int(rbg), int(xor)

    return count([ln for name in inside for ln in comps[name]]), count(text.splitlines())


def test_345m_step_draws_its_dropout_masks_with_the_bit_generator(topo):
    """The committed 345M recipe (dropout 0.1, selective recompute): each
    of the layer's two masks is ONE ``rng-bit-generator`` of
    u32[16,1024,1024] in the forward loop body and one more in the
    backward's (the rematerialised mask; the transposed ``select`` reuses
    it), the embedding's a fifth outside the loops.  No threefry chain
    (20 rounds of ``xor``; the scalar key splits keep theirs) runs over a
    mask's words: with threefry keys XLA copied one into every consumer of
    a mask, the epilogue of the MLP's ``fc_out`` product among eight
    fusions of the layer loop, half of the step's device time (PERF.md
    section 6, PR 32)."""
    text = _step_345m(topo).as_text()
    (rbg_in_loops, xor_in_loops), (rbg, xor) = _mask_draws(text)
    assert (rbg_in_loops, rbg) == (4, 5)
    assert (xor_in_loops, xor) == (0, 0)


def _flash_sites(text):
    """The compiled step's flash kernel sites by the kernel's name less its
    ``pfx_flash_``: ``{"fwd": [op_name, ...], "bwd_dq": ..., "fwd_bsh": ...}``,
    one entry an HLO instruction (a site in a loop body runs once a layer)."""
    import re

    sites = {}
    for kernel, op_name in re.findall(
            r"%pfx_flash_(\w+?)[.\d]* = .*?op_name=\"([^\"]*)\"", text):
        sites.setdefault(kernel, []).append(op_name)
    return sites


def test_345m_step_runs_the_flash_forward_once_a_layer(topo):
    """The committed 345M recipe ("selective" recompute): the compiled step
    holds exactly ONE ``pfx_flash_fwd`` site, in the forward loop body, and
    ONE backward site, ``pfx_flash_bwd_fused`` (``_bwd_schedule``: no window,
    no shared KV heads, seq 1,024 at head 64; before PR 52 a
    ``pfx_flash_bwd_dq`` and a ``pfx_flash_bwd_dkv`` site, every score tile
    computed twice).  The kernel's output is a saved residual
    (``attn_out``, as the XLA and ring paths' is); before
    PR 49 only ``attn_lse`` carried a name and the backward loop re-ran the
    whole forward kernel for ``out``: a second site, 26 ms of a 507 ms step
    (PERF.md section 6, PR 49).

    The price is memory, held here to a figure.  The residual is stacked in
    the model's layout with the heads folded into the minor dimension,
    ``bf16[24,16,1024,1024]``, 768 MiB (the kernel's own
    ``bf16[24,256,1024,64]`` would be padded to 128 lanes: 1,536 MiB), and
    the compiler's peak for the step reads 14,014,113,280 bytes (13.05 GiB)
    where the parent's read 13,208,806,912 (12.30 GiB), of the 15.75 GiB it
    may use; its buffer assignment's total 14,440,171,088 against
    13,634,864,704, so 2.3 GiB are left.  (``argument + temp + generated
    code``, the sum the trinity case bounds, reads 18,706,150,912 against
    17,095,950,336 here: it counts more than the device holds at once, so it
    is stated and not compared with the device's size.)  With the single
    backward kernel (PR 52) the peak reads the same 14,014,113,280 (it is at
    the head, not in the layer loop) and the sum 18,706,032,128.

    Since PR 56 both sites are the kernels in the MODEL's layout
    (``_operand_layout``: head 64, seq 1,024, bfloat16, no window, equal head
    counts): ``pfx_flash_fwd_bsh`` and ``pfx_flash_bwd_fused_bsh`` on
    ``bf16[16,1024,1024]``, lse and delta ``f32[16,8,2,1024]``; nothing in the
    step has the kernels' old ``[256, 1024, 64]`` or a ``[256, 1024, 1]``
    column.  The peak reads 14,047,475,200 on this tree and on its parent."""
    c = _step_345m(topo)
    text = c.as_text()
    sites = _flash_sites(text)
    assert {k: len(v) for k, v in sites.items()} == {"fwd_bsh": 1, "bwd_fused_bsh": 1}
    assert "rematted_computation" not in sites["fwd_bsh"][0]
    assert "bf16[24,256,1024,64]" not in text  # no stack of the padded layout
    assert "[256,1024,64]" not in text and "f32[256,1024,1]" not in text  # nor the layout itself
    assert "f32[16,8,2,1024]" in text  # the statistics with the sequence in the lanes
    m = c.memory_analysis()
    assert m.peak_memory_in_bytes <= 14.1e9, m.peak_memory_in_bytes
    held = m.argument_size_in_bytes + m.temp_size_in_bytes + m.generated_code_size_in_bytes
    assert held <= 18.8e9, held


def _ln_sites(text):
    """The compiled program's LayerNorm kernel sites, ``{"fwd": [op_name, ...],
    "bwd": [...]}``, one entry a Mosaic call (jax names the instruction after
    the kernel with what transformed it in front, ``jvp_pfx_ln_fwd_``, so the
    ``op_name`` is read)."""
    import re

    sites = {}
    for line in text.splitlines():
        if " custom-call(" not in line:
            continue
        m = re.search(r'op_name="([^"]*\bpfx_ln_(fwd|bwd)\b[^"]*/pallas_call)"', line)
        if m:
            sites.setdefault(m.group(2), []).append(m.group(1))
    return sites


def _operand_ops(text, op_name_part):
    """The opcodes that produce the operands of the Mosaic calls whose
    ``op_name`` holds ``op_name_part``, looked up through what only re-views
    or moves a value (``bitcast``, ``copy``, a tuple's element)."""
    import re

    defined = {m.group(1): (m.group(2), m.group(3)) for m in re.finditer(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = .*? ([\w\-]+)\(([^\n]*)", text, re.M)}

    def producer(name):
        op, rest = defined.get(name, ("?", ""))
        while op in ("bitcast", "copy", "copy-start", "copy-done", "get-tuple-element"):
            name = re.search(r"%[\w.\-]+", rest).group(0)
            op, rest = defined.get(name, ("?", ""))
        return op

    ops = set()
    for line in text.splitlines():
        if " custom-call(" in line and op_name_part in line:
            args = line.split(" custom-call(", 1)[1].split(")", 1)[0]
            ops |= {producer(name) for name in re.findall(r"%[\w.\-]+", args)}
    return ops


def test_345m_step_runs_its_layernorms_in_the_kernel(topo):
    """The committed 345M recipe: ``layer_norm``'s rule names the kernel for
    16,384 rows x 1,024 in bfloat16 (``_norm_schedule``), so the compiled step
    holds FIVE ``pfx_ln_fwd`` sites (``ln_1`` and ``ln_2`` in the forward loop
    body, the two again in the backward loop's recompute, since "selective"
    saves only ``qkv`` / ``attn_out`` / ``attn_lse``, and ``final_ln`` at the
    head) and THREE ``pfx_ln_bwd`` sites (the two of the backward loop and
    ``final_ln``'s).  The flash sites are what they were.

    Memory: the compiler's peak reads 14,047,476,224 bytes where the
    composite's read 14,014,113,280 (the peak is at the head: ``final_ln``'s
    kernel writes its ``bf16[16384,1024]`` output, 33.5 MB, to a buffer of
    its own where the composite's was fused into its neighbours; the VJP's
    residuals are x and scale alone, so nothing lane-padded is held: with
    the kernel that saved mean / rstd as two ``f32[16384,1]`` columns the
    peak read 14,064,253,440), the held sum 18,671,142,400 against
    18,706,032,128; both under the bounds
    ``test_345m_step_runs_the_flash_forward_once_a_layer`` holds them to."""
    c = _step_345m(topo)
    text = c.as_text()
    sites = _ln_sites(text)
    assert {k: len(v) for k, v in sites.items()} == {"fwd": 5, "bwd": 3}
    recomputed = [s for s in sites["fwd"] if "rematted_computation" in s]
    in_loops = [s for s in sites["fwd"] if "/while/body/" in s]
    assert (len(recomputed), len(in_loops)) == (2, 4)
    assert sum("/while/body/" in s for s in sites["bwd"]) == 2
    assert {k: len(v) for k, v in _flash_sites(text).items()} == {"fwd_bsh": 1, "bwd_fused_bsh": 1}
    assert c.memory_analysis().peak_memory_in_bytes <= 14.1e9


@pytest.mark.parametrize("name", ["trinity-mini.train_step", "gpt-1.3b.step", "gpt-1.3b.prefill"])
def test_programs_off_the_norm_table_hold_no_layernorm_kernel(topo, name):
    """``train-trinity-mini-1of8`` norms with ``rms_norm``; ``serve-1.3b-docs``
    runs ``layer_norm`` at 8 rows a decode step and one prompt a prefill,
    shapes ``_norm_schedule``'s table does not hold: the programs the cells
    run (``tools/program_text.py`` lowers them as the cells shape them) name
    no ``pfx_ln_*`` kernel, and the 345M step's does."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(_SINGLE_YAML), "..", "..", "tools"))
    import program_text

    root = os.path.dirname(program_text.HERE)
    (_, program), = program_text.lowered(root, (name,))
    assert "pfx_ln_" not in program.as_text()  # noqa: E10 — a kernel's name


def test_trinity_step_still_recomputes_its_flash_forward(topo):
    """"full" recompute saves nothing, names or none: every one of the trinity
    step's backward sites keeps TWO forward sites, the forward pass's and the
    recompute's (``rematted_computation`` in its ``op_name``).  PR 49's name
    on the attention's output saves nothing here."""
    sites = _flash_sites(_step_trinity(topo).as_text())
    forward = sites["fwd"]
    recomputed = [s for s in forward if "rematted_computation" in s]
    assert len(sites["bwd_dq"]) == len(sites["bwd_dkv"]) == 5
    assert len(recomputed) == 5 and len(forward) == 10


def test_trinity_step_draws_no_mask(topo):
    """``hidden_dropout_prob`` 0.0: ``dropout()`` returns its input before
    it touches the key, so the step holds no generator of either kind."""
    text = _step_trinity(topo).as_text()
    assert _mask_draws(text) == ((0, 0), (0, 0))
    assert "rng-bit-generator" not in text


def test_four_chip_step_with_dropout_takes_the_bit_generator(topo):
    """dp 2 x mp 2 of the same recipe: the partitioner takes the
    instruction.  It does NOT split it: every device draws the whole
    batch's u32[16,1024,1024] and keeps its rows (so a mask is the same
    tensor under every layout, and a chip of dp N draws N times its own
    share: ROADMAP queue 1 item 2)."""
    c = _compile_train_step(
        topo, 4, ["Distributed.dp_degree=2", "Distributed.mp_degree=2",
                  "Global.local_batch_size=8", "Global.micro_batch_size=8"])
    text = c.as_text()
    (rbg_in_loops, xor_in_loops), (rbg, xor) = _mask_draws(text)
    assert (rbg_in_loops, rbg) == (4, 5)
    assert (xor_in_loops, xor) == (0, 0)
    import re

    drawn = set(re.findall(r"= (u32\[[\d,]+\])\S* rng-bit-generator\(", text))
    assert drawn == {"u32[16,1024,1024]"}, drawn  # the global batch, on every device
    assert "all-reduce" in text and _has_kernel(c)
    # a shard's 8 x 1,024 rows are of ``_norm_schedule``'s table: the norms run
    # the kernel inside ``shard_map``, on rows as they lie (nothing gathered)
    assert {k: len(v) for k, v in _ln_sites(text).items()} == {"fwd": 5, "bwd": 3}
    assert "bf16[8192,1024]" in text
    assert "all-gather" not in _operand_ops(text, "pfx_ln_")  # noqa: E10 — kernel names


@pytest.mark.slow  # 20-35 s of TPU compile each; run when a layout changes
@pytest.mark.parametrize("overrides", [
    pytest.param(("Distributed.dp_degree=4", 4, 4, 4096), id="dp4"),
    pytest.param(("Distributed.dp_degree=2", "Distributed.mp_degree=2", 8, 8, 8192),
                 id="dp2mp2"),
    pytest.param(("Distributed.dp_degree=2", "Distributed.mp_degree=2",
                  "Distributed.sequence_parallel=True",
                  "Model.sequence_parallel=True", 8, 8, 4096), id="dp2mp2sp"),
    pytest.param(("Distributed.sharding.sharding_degree=4",
                  "Distributed.sharding.sharding_stage=3", 4, 4, 4096), id="zero3x4"),
    pytest.param(("Distributed.dp_degree=2", "Distributed.pp_degree=2", 8, 2, 0),
                 id="dp2pp2"),
])
def test_four_chip_train_step_compiles_with_kernel(topo, overrides):
    """The single-chip config's global batch of 16 spread over four chips:
    ``overrides`` ends with the (local, micro) batch sizes of the layout and
    the rows of one shard's LayerNorm where ``_norm_schedule`` names the
    kernel for them (0: a micro batch of the pipeline is 1,024 rows a shard,
    off the table).  The kernel sits inside ``shard_map`` (bare, Mosaic
    "cannot be automatically partitioned") and reads its rows as they lie:
    no ``all-gather`` defines an operand of it."""
    *overrides, local, micro, ln_rows = overrides
    overrides += [f"Global.local_batch_size={local}",
                  f"Global.micro_batch_size={micro}"]
    c = _compile_train_step(topo, 4, overrides)
    text = c.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    sites = {k: len(v) for k, v in _ln_sites(text).items()}
    assert sites == ({"fwd": 5, "bwd": 3} if ln_rows else {})
    if ln_rows:
        assert f"bf16[{ln_rows},1024]" in text
        assert "all-gather" not in _operand_ops(text, "pfx_ln_")  # noqa: E10 — kernel names
    # one shard's 16 or 8 heads of 64 fill whole 128-lane blocks: inside
    # ``shard_kernel`` the flash kernels are handed the model's layout
    assert set(_flash_sites(text)) == {"fwd_bsh", "bwd_fused_bsh"}


def _docs_step_13b(topo, kv_dtype="bf16", t=1, donate=False):
    """The docs cell's decode step (GPT-1.3B, 8 rows of 1024 tokens, 16-token
    blocks; t > 1: its verify chunk) compiled for one described chip, with
    the tree the server holds (``serving_params``) -> (compiled, weights'
    bytes, arena's bytes)."""
    from paddlefleetx_tpu.models.gpt.config import GPTConfig
    from paddlefleetx_tpu.models.gpt.generation import (
        init_paged_pools,
        paged_forward_step,
        serving_params,
    )

    cfg = GPTConfig(
        hidden_size=2048, num_layers=24, num_attention_heads=16,
        ffn_hidden_size=8192, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, dtype="bfloat16",
    )
    one = _one_chip(topo)
    held = _shapes(one, jax.eval_shape(
        lambda p: serving_params(p, cfg), _param_shapes(cfg, one)))
    rows, bs, ctx_len = 8, 16, cfg.max_position_embeddings
    pools = _shapes(one, jax.eval_shape(
        lambda: init_paged_pools(cfg, rows * (ctx_len // bs) + 1, bs, kv_dtype=kv_dtype)))
    c = jax.jit(
        lambda p, pools, toks, tb, ps, act: paged_forward_step(
            p, toks, pools, tb, ps, act, cfg),
        donate_argnums=(1,) if donate else (),
    ).lower(
        held, pools, _shapes(one, ((rows, t), jnp.int32)),
        _shapes(one, ((rows, ctx_len // bs), jnp.int32)),
        _shapes(one, ((rows,), jnp.int32)), _shapes(one, ((rows,), jnp.bool_)),
    ).compile()
    assert _has_kernel(c)

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    return c, nbytes(held), nbytes(pools)


def test_decode_step_13b_holds_one_copy_of_the_weights(topo):
    """The docs cell's decode step as the server dispatches it: with the
    tree it holds (``serving_params``) the program's scratch has no second,
    converted copy of the weights.  With the float32 tree ``temp`` was
    2.62 GB beside 6.87 GB of arguments (``pfx_bench/selftest/chip_compile.py
    serve gpt-1.3b 8 512,960``, which still passes float32 shapes)."""
    c, weights, _ = _docs_step_13b(topo)
    assert 2.6e9 < weights < 2.7e9
    m = c.memory_analysis()
    # weights 2.63 GB + arena 1.61 GB + the small operands
    assert 4.2e9 < m.argument_size_in_bytes < 4.4e9
    # no converted copy of any stacked weight: the largest is 0.8 GB in bf16
    assert m.temp_size_in_bytes < 0.5e9
    text = c.as_text()
    assert "bf16[24,2048,8192]{" in text  # the held fc_in, an operand as it is
    assert "f32[24,2048,8192]" not in text and "f32[50304,2048]" not in text


@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_decode_step_13b_writes_the_donated_arena_in_place(topo, kv_dtype, t):
    """The docs cell's step (and its verify chunk) as the scheduler
    dispatches it, the pools DONATED: the arena is carried through the
    layer loop, the step's K/V are scattered into it where it lies and the
    kernel reads each layer's pages out of it.  Nothing arena-shaped is
    copied, sliced out of the stack or written back into it, so the
    program needs no scratch to speak of.  With the pools as the scan's
    xs / ys the same compile held two more arenas as scratch (``temp``
    1.614 GB in bf16) and each step moved 1.6 GB twice."""
    import re

    c, _, arena = _docs_step_13b(topo, kv_dtype, t, donate=True)
    m = c.memory_analysis()
    # the output IS the donated input (the int8 arena's scale planes are
    # held in padded tiles, 0.8% more than their values)
    assert arena <= m.alias_size_in_bytes < 1.01 * arena
    assert m.temp_size_in_bytes < 0.1e9
    # every instruction whose result has the shape of the arena, of one
    # layer's pool or of either viewed with layers and blocks merged
    # (the scale planes lack the last dimension)
    dims = "|".join(
        f"{lead},16,16(?:,128)?" for lead in ("24,513", "513", 24 * 513))
    moved = re.findall(
        rf"= \w+\[(?:{dims})\]\S* "
        r"(copy|dynamic-slice|dynamic-update-slice|custom-call)\(([^\n]*)",
        c.as_text(),
    )
    moved = [
        op for op, rest in moved
        if op != "custom-call" or 'custom_call_target="AllocateBuffer"' in rest
    ]
    assert not moved, moved


# ---------------------------------------------------------------------------
# A block with row state (docs/nemotron_h.md) at the published sizes of the
# benchmark's configuration nemotron-3-nano: the state kernels, the paged
# kernel over pools of 2 KV heads, and the whole 52-layer decode step
# ---------------------------------------------------------------------------


def _ssm_decode_calls(text):
    """The compiled program's custom calls named as the trace names them: the
    third operand of each (after the grid's bound and the layer) is the live list."""
    import re

    return re.findall(r"%pfx_ssm_decode\S* = [^\n]*tpu_custom_call", text), set(
        re.findall(r"%pfx_ssm_decode\S* = [^\n]*custom-call\(\S+ \S+ (\S+),", text))


def test_ssm_state_kernels_rewrite_the_states_in_place(topo):
    """``pfx_ssm_decode`` over the LIVE slots of two layers of the 23 x 48
    slots' states (2.3 GB, donated; the live list made once for both) and
    ``pfx_ssm_write`` of one slot: the programs need NO scratch and copy
    nothing state-shaped — a copy of the states does not fit beside 10.5 GB
    of weights."""
    import re

    from paddlefleetx_tpu.ops import ssm

    one = _one_chip(topo)
    slots, heads, hd, n, groups = 48, 64, 64, 128, 8
    states = _shapes(one, ((23, slots) + ssm.packed_shape(heads, hd, n), jnp.float32))

    def two_layers(st, x, dt, a, b, c, d, active):
        live = ssm.live_slots(active)
        for layer in range(2):
            y, st = ssm.ssm_decode_update(st, x, dt, a, b, c, d, active=active, layer=layer,
                                          live=live)
            x = x + y.astype(x.dtype)
        return y, st

    args = _shapes(one, (((slots, heads, hd), BF16), ((slots, heads), jnp.float32),
                         ((heads,), jnp.float32), ((slots, groups, n), BF16),
                         ((slots, groups, n), BF16), ((heads,), jnp.float32),
                         ((slots,), jnp.bool_)))
    c = jax.jit(two_layers, donate_argnums=(0,)).lower(states, *args).compile()
    text = c.as_text()
    assert text.count("tpu_custom_call") == 2
    calls, lists = _ssm_decode_calls(text)
    assert len(calls) == 2 and len(lists) == 1, lists  # the trace's name; ONE live list for both
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < 1e6 and m.alias_size_in_bytes >= 23 * slots * 2 ** 21
    moved = re.findall(r"= \w+\[23,48,32,128,128\]\S* (copy|transpose|dynamic-update-slice)\(", text)
    assert not moved, moved
    new = _shapes(one, ((23,) + ssm.packed_shape(heads, hd, n), jnp.float32))
    slot = _shapes(one, ((), jnp.int32))
    w = jax.jit(ssm.write_slot_states, donate_argnums=(0,)).lower(states, new, slot).compile()
    assert _has_kernel(w) and w.memory_analysis().temp_size_in_bytes < 1e6


@pytest.mark.parametrize("rows,t,width", [pytest.param(48, 1, 16, id="chat-cell"),
                                          pytest.param(4, 3, 8, id="chunk-of-3")])
def test_paged_decode_with_shared_kv_heads_compiles(topo, rows, t, width):
    """32 query heads on the 2 KV heads of a [6, 673, 2, 128, 128] arena: the
    16 queries of a KV head are the rows of one product against its page."""
    from paddlefleetx_tpu.ops.decode_attention import paged_decode_attention

    one = _one_chip(topo)
    q = _shapes(one, ((rows, t, 32, 128), BF16))
    pool = _shapes(one, ((6, 673, 2, 128, 128), BF16))
    tables = _shapes(one, ((rows, width), jnp.int32))
    positions = _shapes(one, ((rows,), jnp.int32))
    c = _compile(lambda q, k, v, tb, ps: paged_decode_attention(q, k, v, tb, ps, layer=3),
                 q, pool, pool, tables, positions)
    assert _has_kernel(c) and c.memory_analysis().temp_size_in_bytes < 16e6


def test_decode_step_of_the_whole_depth_pattern_block_fits_and_copies_nothing(topo):
    """The benchmark cell ``serve-nemotron3-nano-1of8-chat``'s decode step as
    its configuration file states it (52 layers, 48 slots), the pools DONATED:
    10.5 GB of weights, 2.36 GB of states and 0.53 GB of pages are arguments,
    the states and the arena come back aliased, and nothing of their shape is
    copied (the step's scratch is tens of MB)."""
    import json
    import re

    from paddlefleetx_tpu.models.gpt import generation as G
    from paddlefleetx_tpu.models.gpt.config import GPTConfig

    root = os.path.join(os.path.dirname(_SINGLE_YAML), "..", "..")
    bench = os.path.join(root, "pfx_bench")  # noqa: E10 — a directory, not a metric
    with open(os.path.join(bench, "configs", "nemotron-3-nano.json")) as f:
        cfg = GPTConfig(**json.load(f)["model"])
    one = _one_chip(topo)
    slots, width, vocab = 48, 16, cfg.vocab_size
    params = _shapes(one, jax.eval_shape(lambda: G.init_serving_params(cfg, jax.random.key(0))))
    pools = _shapes(one, jax.eval_shape(
        lambda: G.init_paged_pools(cfg, slots * 14 + 1, cfg.kv_block_default, slots=slots)))
    gen = G.GenerationConfig(decode_strategy="greedy_search", max_dec_len=0, min_dec_len=768,
                             eos_token_id=0, pad_token_id=0)

    def step(p, pools, tables, logits, counts, positions, gen_steps, max_news, active, forced):
        rows = G.PagedRows(logits, counts, positions, gen_steps, max_news, active, forced)
        nxt, pools, new = G.decode_step(p, pools, tables, rows, cfg, gen)
        return nxt, pools, new.logits, new.counts, new.moe

    i32 = functools.partial(lambda *shape: (shape, jnp.int32))
    rows = _shapes(one, (i32(slots, width), ((slots, vocab), jnp.float32), i32(slots, vocab),
                         i32(slots), i32(slots), i32(slots), ((slots,), jnp.bool_), i32(slots)))
    c = jax.jit(step, donate_argnums=(1,)).lower(params, pools, *rows).compile()
    m = c.memory_analysis()
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(pools))
    assert 2.8e9 < held <= m.alias_size_in_bytes < 1.01 * held
    assert 13.3e9 < m.argument_size_in_bytes < 13.6e9 and m.temp_size_in_bytes < 0.2e9
    text = c.as_text()
    assert text.count("tpu_custom_call") == 23 + 6  # a state update a Mamba layer, a paged read a * layer
    calls, lists = _ssm_decode_calls(text)
    # the live list is made once: 23 calls read ONE compacted list of slots
    assert len(calls) == 23 and len(lists) == 1, lists
    moved = re.findall(r"= \w+\[(?:23,48,32,128,128|6,673,2,128,128)\]\S* (copy|transpose)\(", text)
    assert not moved, moved


# ---------------------------------------------------------------------------
# The serving prefills of the two expert configurations: the products over the
# sorted pairs run in pfx_grouped_matmul and read the experts' matrices where
# the served tree keeps them
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("config,bucket,slots,blocks,products,fits", [
    pytest.param("nemotron-3-nano", 256, 48, 673, 46, 13.6e9 + 0.2e9, id="chat-cell-256"),
    pytest.param("deepseek-v3", 1024, 64, 2305, 18, 11.8e9, id="think-cell-1024"),
])
def test_serving_prefill_runs_its_grouped_products_in_the_kernel(topo, config, bucket, slots, blocks,
                                                                 products, fits):
    """One bucket of each expert cell's prefill program as its configuration
    file states it, the pools DONATED: every expert layer's matrices go through
    ``pfx_grouped_matmul`` (46 = 23 layers x 2 relu2 matrices, 18 = 6 x 3
    SwiGLU), XLA's ``ragged-dot`` is gone, and no experts' matrix is copied,
    transposed or padded on its way in: ``bf16[16,2688,1856]``, whose last
    dimension is no multiple of the 128 lanes, lies on the chip with the 2688
    minor, and the kernel contracts it there (ops/grouped_matmul.py).  Arguments
    and scratch stay inside what the chat cell's decode step is allowed above
    (13.6 + 0.2 GB; with ``ragged-dot`` and its copies the scratch was 0.39 GB)."""
    import json
    import re

    from paddlefleetx_tpu.models.gpt import generation as G
    from paddlefleetx_tpu.models.gpt.config import GPTConfig

    root = os.path.join(os.path.dirname(_SINGLE_YAML), "..", "..")
    bench = os.path.join(root, "pfx_bench")  # noqa: E10 — a directory, not a metric
    with open(os.path.join(bench, "configs", f"{config}.json")) as f:
        cfg = GPTConfig(**json.load(f)["model"])
    one = _one_chip(topo)
    params = _shapes(one, jax.eval_shape(lambda: G.init_serving_params(cfg, jax.random.key(0))))
    pools = _shapes(one, jax.eval_shape(
        lambda: G.init_paged_pools(cfg, blocks, cfg.kv_block_default, slots=slots)))
    i32 = lambda *shape: (shape, jnp.int32)  # noqa: E731

    def prefill(p, prompt, plen, pools, row, slot):
        row_state = {"slot": slot} if cfg.layer_pattern else {}
        return G.paged_prefill(p, prompt, plen, pools, row, cfg, return_moe=True, **row_state)

    prompt, plen, row, slot = _shapes(one, (i32(1, bucket), i32(), i32(bucket // 128), i32()))
    c = jax.jit(prefill, donate_argnums=(3,)).lower(params, prompt, plen, pools, row, slot).compile()
    text = c.as_text()
    assert len(re.findall(r"%pfx_grouped_matmul\S* = ", text)) == products
    assert "ragged-dot" not in text and "ragged_dot" not in text
    e, h, f = cfg.experts_held, cfg.hidden_size, cfg.moe_ffn_hidden_size
    moved = re.findall(rf"= \w+\[{e},(?:{h},{f}|{f},{h})\]\S* (\w[\w-]*)\(", text)
    # parameters, renamed for the kernel where they lie k-minor, and nothing else
    assert set(moved) <= {"parameter", "bitcast"}, set(moved)
    m = c.memory_analysis()
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(pools))
    assert held <= m.alias_size_in_bytes < 1.01 * held
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < fits, m.temp_size_in_bytes


@pytest.mark.parametrize("width", [16, 32, 64])
def test_decode_step_of_the_latent_block_runs_its_kernel_over_the_work_list(topo, width):
    """The benchmark cell ``serve-dsv3-1of32-think``'s decode step as its
    configuration file states it (7 layers, 64 slots, 2,305 latent pages), the
    pools DONATED, at each table width the cell's rows make (12-36 pages a
    row: buckets 16, 32 and 64; the warm request makes 32, the engine's own
    warm-up 64): the seven ``pfx_decode_mla_paged`` calls compile with ONE grid
    axis whose bound is the work list's count (Mosaic takes the dynamic bound
    and the prefetched rows and groups in every address), the latent arena
    comes back aliased and is never copied."""
    import json
    import re

    from paddlefleetx_tpu.models.gpt import generation as G
    from paddlefleetx_tpu.models.gpt.config import GPTConfig

    root = os.path.join(os.path.dirname(_SINGLE_YAML), "..", "..")
    bench = os.path.join(root, "pfx_bench")  # noqa: E10 — a directory, not a metric
    with open(os.path.join(bench, "configs", "deepseek-v3.json")) as f:
        cfg = GPTConfig(**json.load(f)["model"])
    slots, blocks, vocab = 64, 2305, cfg.vocab_size
    one = _one_chip(topo)
    params = _shapes(one, jax.eval_shape(lambda: G.init_serving_params(cfg, jax.random.key(0))))
    pools = _shapes(one, jax.eval_shape(
        lambda: G.init_paged_pools(cfg, blocks, cfg.kv_block_default, slots=slots)))
    gen = G.GenerationConfig(decode_strategy="greedy_search", max_dec_len=0, min_dec_len=1536,
                             eos_token_id=0, pad_token_id=0)

    def step(p, pools, tables, logits, counts, positions, gen_steps, max_news, active, forced):
        rows = G.PagedRows(logits, counts, positions, gen_steps, max_news, active, forced)
        nxt, pools, new = G.decode_step(p, pools, tables, rows, cfg, gen)
        return nxt, pools, new.logits, new.counts, new.moe

    i32 = lambda *shape: (shape, jnp.int32)  # noqa: E731
    rows = _shapes(one, (i32(slots, width), ((slots, vocab), jnp.float32), i32(slots, vocab),
                         i32(slots), i32(slots), i32(slots), ((slots,), jnp.bool_), i32(slots)))
    c = jax.jit(step, donate_argnums=(1,)).lower(params, pools, *rows).compile()
    text = c.as_text()
    assert len(re.findall(r"%pfx_decode_mla_paged\S* = ", text)) == 7
    assert len(re.findall(r"%pfx_mla_write\S* = ", text)) == 7
    m = c.memory_analysis()
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(pools))
    assert held == 7 * blocks * 576 * 128 * 2 and held <= m.alias_size_in_bytes < 1.01 * held
    moved = re.findall(rf"= \w+\[7,{blocks},1,576,128\]\S* (copy|transpose)\(", text)
    assert not moved, moved
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 11.8e9, m.temp_size_in_bytes


# ---------------------------------------------------------------------------
# A block whose every layer keeps BOTH a recurrent state and pages
# (docs/falcon_h1.md) at the published sizes of the benchmark's configuration
# falcon-h1-34b: the state kernels at state 256 on heads of 128, the paged
# kernel at 5 query heads a KV head, the 12-sub-block decode step and the one
# prefill bucket of the cell
# ---------------------------------------------------------------------------


def _falcon_cfg():
    import json

    from paddlefleetx_tpu.models.gpt.config import GPTConfig

    root = os.path.join(os.path.dirname(_SINGLE_YAML), "..", "..")
    bench = os.path.join(root, "pfx_bench")  # noqa: E10 — a directory, not a metric
    with open(os.path.join(bench, "configs", "falcon-h1-34b.json")) as f:
        return GPTConfig(**json.load(f)["model"])


def test_ssm_state_kernels_at_state_256_on_heads_of_128(topo):
    """``pfx_ssm_decode`` / ``pfx_ssm_write`` over [6, 64, R 32, state 256, W
    128] float32 (1.6 GB, donated): a grid step holds 8 lane groups (1 MB in,
    1 MB out: ``_STEP_BYTES``, where 16 groups would be 2 + 2 MB twice over
    for the pipeline), the states come back aliased and no copy is made."""
    import re

    from paddlefleetx_tpu.ops import ssm

    one = _one_chip(topo)
    slots, heads, hd, n, groups = 64, 32, 128, 256, 2
    assert ssm.packed_shape(heads, hd, n) == (32, 256, 128)
    assert ssm._groups_per_step(32, 256 * 128 * 4) == 8 and ssm._groups_per_step(32, 2 ** 16) == 16
    states = _shapes(one, ((6, slots) + ssm.packed_shape(heads, hd, n), jnp.float32))

    def two_layers(st, x, dt, a, b, c, d, active):
        live = ssm.live_slots(active)
        for layer in range(2):
            y, st = ssm.ssm_decode_update(st, x, dt, a, b, c, d, active=active, layer=layer,
                                          live=live)
            x = x + y.astype(x.dtype)
        return y, st

    args = _shapes(one, (((slots, heads, hd), BF16), ((slots, heads), jnp.float32),
                         ((heads,), jnp.float32), ((slots, groups, n), BF16),
                         ((slots, groups, n), BF16), ((heads,), jnp.float32),
                         ((slots,), jnp.bool_)))
    c = jax.jit(two_layers, donate_argnums=(0,)).lower(states, *args).compile()
    text = c.as_text()
    calls, lists = _ssm_decode_calls(text)
    assert len(calls) == 2 and len(lists) == 1, lists
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < 1e6 and m.alias_size_in_bytes >= 6 * slots * 2 ** 22
    moved = re.findall(r"= \w+\[6,64,32,256,128\]\S* (copy|transpose|dynamic-update-slice)\(", text)
    assert not moved, moved
    new = _shapes(one, ((6,) + ssm.packed_shape(heads, hd, n), jnp.float32))
    slot = _shapes(one, ((), jnp.int32))
    w = jax.jit(ssm.write_slot_states, donate_argnums=(0,)).lower(states, new, slot).compile()
    assert _has_kernel(w) and w.memory_analysis().temp_size_in_bytes < 1e6


def test_paged_decode_with_five_query_heads_a_kv_head_compiles(topo):
    """20 query heads on the 4 KV heads of a [6, 385, 4, 128, 128] arena: the 5
    queries of a KV head are the rows of one product against its page."""
    from paddlefleetx_tpu.ops.decode_attention import paged_decode_attention

    one = _one_chip(topo)
    q = _shapes(one, ((64, 1, 20, 128), BF16))
    pool = _shapes(one, ((6, 385, 4, 128, 128), BF16))
    tables = _shapes(one, ((64, 6), jnp.int32))
    positions = _shapes(one, ((64,), jnp.int32))
    c = _compile(lambda q, k, v, tb, ps: paged_decode_attention(q, k, v, tb, ps, layer=3),
                 q, pool, pool, tables, positions)
    assert _has_kernel(c) and c.memory_analysis().temp_size_in_bytes < 16e6


def _falcon_shapes(topo, cfg, slots):
    from paddlefleetx_tpu.models.gpt import generation as G

    one = _one_chip(topo)
    params = _shapes(one, jax.eval_shape(lambda: G.init_serving_params(cfg, jax.random.key(0))))
    pools = _shapes(one, jax.eval_shape(
        lambda: G.init_paged_pools(cfg, slots * 6 + 1, cfg.kv_block_default, slots=slots)))
    return one, params, pools


def test_decode_step_of_the_parallel_block_fits_and_copies_nothing(topo):
    """The benchmark cell ``serve-falcon-h1-34b-6of72-chat``'s decode step as
    its configuration file states it (6 published layers = 12 sub-blocks, 64
    slots, the 261,120-row head), the pools DONATED: 10.5 GB of weights, 1.6 GB
    of states and 0.6 GB of pages are arguments, the states and the arena come
    back aliased and nothing of their shape is copied."""
    import re

    from paddlefleetx_tpu.models.gpt import generation as G

    cfg = _falcon_cfg()
    slots, width, vocab = 64, 6, cfg.vocab_size
    one, params, pools = _falcon_shapes(topo, cfg, slots)
    gen = G.GenerationConfig(decode_strategy="greedy_search", max_dec_len=0, min_dec_len=512,
                             eos_token_id=0, pad_token_id=0)

    def step(p, pools, tables, logits, counts, positions, gen_steps, max_news, active, forced):
        rows = G.PagedRows(logits, counts, positions, gen_steps, max_news, active, forced)
        nxt, pools, new = G.decode_step(p, pools, tables, rows, cfg, gen)
        return nxt, pools, new.logits, new.counts, new.moe

    i32 = functools.partial(lambda *shape: (shape, jnp.int32))
    rows = _shapes(one, (i32(slots, width), ((slots, vocab), jnp.float32), i32(slots, vocab),
                         i32(slots), i32(slots), i32(slots), ((slots,), jnp.bool_), i32(slots)))
    c = jax.jit(step, donate_argnums=(1,)).lower(params, pools, *rows).compile()
    m = c.memory_analysis()
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(pools))
    assert 2.2e9 < held <= m.alias_size_in_bytes < 1.01 * held
    assert 12.8e9 < m.argument_size_in_bytes < 13.1e9 and m.temp_size_in_bytes < 0.6e9
    text = c.as_text()
    assert text.count("tpu_custom_call") == 6 + 6  # a state update AND a paged read a P layer
    # (the live list reaches some calls through a prefetch copy of its 64 numbers,
    # so the operand's name does not show that it is made once; the nemotron case does)
    assert len(_ssm_decode_calls(text)[0]) == 6
    assert len(re.findall(r"%pfx_decode_paged\S* = ", text)) == 6
    moved = re.findall(r"= \w+\[(?:6,64,32,256,128|6,385,4,128,128)\]\S* (copy|transpose)\(", text)
    assert not moved, moved


def test_prefill_of_the_parallel_block_at_the_cell_s_one_bucket(topo):
    """The 256-token prefill (the cell's only bucket), pools DONATED: flash at
    20/4, the chunked scan at state 256, the states written by ``pfx_ssm_write``
    and the rotated keys into the row's pages; no whole-pool copy."""
    import re

    from paddlefleetx_tpu.models.gpt import generation as G

    cfg = _falcon_cfg()
    one, params, pools = _falcon_shapes(topo, cfg, 64)
    i32 = lambda *shape: (shape, jnp.int32)  # noqa: E731

    def prefill(p, prompt, plen, pools, row, slot):
        return G.paged_prefill(p, prompt, plen, pools, row, cfg, return_moe=True, slot=slot)

    prompt, plen, row, slot = _shapes(one, (i32(1, 256), i32(), i32(2), i32()))
    c = jax.jit(prefill, donate_argnums=(3,)).lower(params, prompt, plen, pools, row, slot).compile()
    text = c.as_text()
    assert len(re.findall(r"%pfx_flash_fwd\S* = ", text)) == 6
    assert len(re.findall(r"%pfx_ssm_write\S* = ", text)) == 1
    moved = re.findall(r"= \w+\[(?:6,64,32,256,128|6,385,4,128,128)\]\S* (copy|transpose)\(", text)
    assert not moved, moved
    m = c.memory_analysis()
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(pools))
    assert held <= m.alias_size_in_bytes < 1.01 * held
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 13.6e9, m.temp_size_in_bytes


# ---------------------------------------------------------------------------
# A block with two KINDS of attention layer and two classes of pages
# (docs/mellum2.md) at the published sizes of the benchmark's configuration
# mellum2-12b-a2.5b: the windowed read of a ring, the 56-sub-block decode step
# and the one prefill bucket of the cell
# ---------------------------------------------------------------------------


def _mellum_cfg():
    import json

    from paddlefleetx_tpu.models.gpt.config import GPTConfig

    root = os.path.join(os.path.dirname(_SINGLE_YAML), "..", "..")
    bench = os.path.join(root, "pfx_bench")  # noqa: E10 — a directory, not a metric
    with open(os.path.join(bench, "configs", "mellum2-12b-a2.5b.json")) as f:
        return GPTConfig(**json.load(f)["model"])


def _mellum_shapes(topo, cfg, slots):
    from paddlefleetx_tpu.models.gpt import generation as G

    one = _one_chip(topo)
    params = _shapes(one, jax.eval_shape(lambda: G.init_serving_params(cfg, jax.random.key(0))))
    pools = _shapes(one, jax.eval_shape(lambda: G.init_paged_pools(
        cfg, slots * 22 + 1, cfg.kv_block_default, ring_blocks=slots * 9 + 1)))
    return one, params, pools


def test_windowed_paged_decode_over_a_ring_compiles(topo):
    """``pfx_decode_window``: 32 query heads on the 4 KV heads of a [21, 433,
    4, 128, 128] ring arena, 9 pages a row turned oldest first, a fourth
    prefetched scalar a row (the first slot the window lets it see)."""
    from paddlefleetx_tpu.ops.decode_attention import paged_decode_attention, window_view

    one = _one_chip(topo)
    q = _shapes(one, ((48, 1, 32, 128), BF16))
    pool = _shapes(one, ((21, 433, 4, 128, 128), BF16))
    rings = _shapes(one, ((48, 9), jnp.int32))
    positions = _shapes(one, ((48,), jnp.int32))

    def read(q, k, v, rings, ps):
        tables, at, starts = window_view(rings, ps, 1024, 128)
        return paged_decode_attention(q, k, v, tables, at, layer=5, starts=starts)

    c = _compile(read, q, pool, pool, rings, positions)
    import re

    text = c.as_text()
    assert re.findall(r"%pfx_decode_window\S* = ", text) and not re.findall(r"%pfx_decode_paged\S* = ", text)
    assert c.memory_analysis().temp_size_in_bytes < 16e6


def test_decode_step_of_the_two_kinds_of_attention_fits_and_copies_nothing(topo):
    """The benchmark cell ``serve-mellum2-12b-1of4-code``'s decode step as its
    configuration file states it (28 published layers = 56 sub-blocks, 48
    slots, tables 32 pages wide, rings of 9), the pools DONATED: 7.66 GB of
    weights and 4.32 GB of pages in two classes are arguments, both arenas come
    back aliased and nothing of their shape is copied; 7 calls of
    ``pfx_decode_paged`` and 21 of ``pfx_decode_window``."""
    import re

    from paddlefleetx_tpu.models.gpt import generation as G

    cfg = _mellum_cfg()
    slots, width, vocab = 48, 32, cfg.vocab_size
    one, params, pools = _mellum_shapes(topo, cfg, slots)
    gen = G.GenerationConfig(decode_strategy="greedy_search", max_dec_len=0, min_dec_len=768,
                             eos_token_id=0, pad_token_id=0)

    def step(p, pools, tables, rings, logits, counts, positions, gen_steps, max_news, active, forced):
        rows = G.PagedRows(logits, counts, positions, gen_steps, max_news, active, forced)
        nxt, pools, new = G.decode_step(p, pools, (tables, rings), rows, cfg, gen)
        return nxt, pools, new.logits, new.counts, new.moe

    i32 = functools.partial(lambda *shape: (shape, jnp.int32))
    rows = _shapes(one, (i32(slots, width), i32(slots, 9), ((slots, vocab), jnp.float32),
                         i32(slots, vocab), i32(slots), i32(slots), i32(slots),
                         ((slots,), jnp.bool_), i32(slots)))
    c = jax.jit(step, donate_argnums=(1,)).lower(params, pools, *rows).compile()
    m = c.memory_analysis()
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(pools))
    assert held == (7 * 1057 + 21 * 433) * 262144 and held <= m.alias_size_in_bytes < 1.01 * held
    assert 11.9e9 < m.argument_size_in_bytes < 12.1e9 and m.temp_size_in_bytes < 0.2e9
    text = c.as_text()
    assert len(re.findall(r"%pfx_decode_paged\S* = ", text)) == 7
    assert len(re.findall(r"%pfx_decode_window\S* = ", text)) == 21
    assert text.count("tpu_custom_call") == 28
    moved = re.findall(r"= \w+\[(?:7,1057,4,128,128|21,433,4,128,128)\]\S* (copy|transpose)\(", text)
    assert not moved, moved


@pytest.mark.slow  # 55 s of TPU compile; run when the prefill or a pool's layout changes
def test_prefill_of_the_two_kinds_of_attention_at_the_cell_s_one_bucket(topo):
    """The 2,048-token prefill (the cell's only bucket), pools DONATED: 28
    flash forwards (21 with the window), 84 grouped products over the sorted
    pairs, the full layers' 16 pages and the window layers' 9 ring pages
    written in place; arguments and scratch fit beside each other."""
    import re

    from paddlefleetx_tpu.models.gpt import generation as G

    cfg = _mellum_cfg()
    one, params, pools = _mellum_shapes(topo, cfg, 48)
    i32 = lambda *shape: (shape, jnp.int32)  # noqa: E731

    def prefill(p, prompt, plen, pools, row, ring):
        return G.paged_prefill(p, prompt, plen, pools, (row, ring), cfg, return_moe=True)

    prompt, plen, row, ring = _shapes(one, (i32(1, 2048), i32(), i32(16), i32(9)))
    c = jax.jit(prefill, donate_argnums=(3,)).lower(params, prompt, plen, pools, row, ring).compile()
    text = c.as_text()
    assert len(re.findall(r"%pfx_flash_fwd\S* = ", text)) == 28
    assert len(re.findall(r"%pfx_grouped_matmul\S* = ", text)) == 84
    moved = re.findall(r"= \w+\[(?:7,1057,4,128,128|21,433,4,128,128)\]\S* (copy|transpose)\(", text)
    assert not moved, moved
    m = c.memory_analysis()
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(pools))
    assert held <= m.alias_size_in_bytes < 1.01 * held
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 13.2e9, m.temp_size_in_bytes


# ---------------------------------------------------------------------------
# The described block over a residual stream of four copies (docs/xing4.md) at
# the published widths of the benchmark's configuration xing4.0-29b-a4b: the
# cell's decode step at both table widths and its one prefill bucket, each
# with 24 calls of the maps' kernels (2 sub-blocks x 6 layers x pre and post)
# ---------------------------------------------------------------------------


def _xing4(topo):
    import json

    from paddlefleetx_tpu.models.gpt import generation as G
    from paddlefleetx_tpu.models.gpt.config import GPTConfig

    root = os.path.join(os.path.dirname(_SINGLE_YAML), "..", "..")
    bench = os.path.join(root, "pfx_bench")  # noqa: E10 — a directory, not a metric
    with open(os.path.join(bench, "configs", "xing4.0-29b-a4b.json")) as f:
        cfg = GPTConfig(**json.load(f)["model"])
    slots, blocks = 64, 64 * 20 + 1  # a row reserves at most 2,560 tokens: 20 pages of 128
    one = _one_chip(topo)
    params = _shapes(one, jax.eval_shape(lambda: G.init_serving_params(cfg, jax.random.key(0))))
    pools = _shapes(one, jax.eval_shape(
        lambda: G.init_paged_pools(cfg, blocks, cfg.kv_block_default, slots=slots)))
    return cfg, one, params, pools, slots, blocks


def _hc_calls(text):
    import re

    return {k: len(re.findall(rf"%pfx_hc_{k}\S* = ", text)) for k in ("pre", "post")}


@pytest.mark.parametrize("width", [16, 32])
def test_decode_step_of_the_four_copy_stream_runs_24_maps_kernels(topo, width):
    """The benchmark cell ``serve-xing4-29b-6of40-rag``'s decode step as its
    configuration file states it (6 layers with all 64 experts, 64 slots,
    1,281 latent pages, the whole 131,072-row vocabulary), the pools DONATED,
    at both table widths the cell's rows make: 12 ``pfx_hc_pre`` and 12
    ``pfx_hc_post`` calls, the latent kernel once a layer, the arena aliased,
    arguments and scratch under 13.5 GB."""
    from paddlefleetx_tpu.models.gpt import generation as G

    cfg, one, params, pools, slots, blocks = _xing4(topo)
    vocab = cfg.vocab_size
    gen = G.GenerationConfig(decode_strategy="greedy_search", max_dec_len=0, min_dec_len=512,
                             eos_token_id=0, pad_token_id=0)

    def step(p, pools, tables, logits, counts, positions, gen_steps, max_news, active, forced):
        rows = G.PagedRows(logits, counts, positions, gen_steps, max_news, active, forced)
        nxt, pools, new = G.decode_step(p, pools, tables, rows, cfg, gen)
        return nxt, pools, new.logits, new.counts, new.moe

    i32 = lambda *shape: (shape, jnp.int32)  # noqa: E731
    rows = _shapes(one, (i32(slots, width), ((slots, vocab), jnp.float32), i32(slots, vocab),
                         i32(slots), i32(slots), i32(slots), ((slots,), jnp.bool_), i32(slots)))
    c = jax.jit(step, donate_argnums=(1,)).lower(params, pools, *rows).compile()
    text = c.as_text()
    assert _hc_calls(text) == {"pre": 12, "post": 12}
    assert len(re.findall(r"%pfx_decode_mla_paged\S* = ", text)) == 6
    m = c.memory_analysis()
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(pools))
    assert held == 6 * blocks * 576 * 128 * 2 and held <= m.alias_size_in_bytes < 1.01 * held
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 13.5e9, m.temp_size_in_bytes


def test_prefill_of_the_four_copy_stream_at_the_cell_s_one_bucket(topo):
    """The cell's ONE prefill bucket (2,048 tokens, 16 pages): 24 calls of the
    maps' kernels over ``[2048, 4 x 3584]``, the five expert layers' products
    over 64 groups in ``pfx_grouped_matmul`` (15 = 5 x 3), arguments and
    scratch under 13.5 GB."""
    from paddlefleetx_tpu.models.gpt import generation as G

    cfg, one, params, pools, _, _ = _xing4(topo)
    i32 = lambda *shape: (shape, jnp.int32)  # noqa: E731
    prompt, plen, row = _shapes(one, (i32(1, 2048), i32(), i32(16)))
    c = jax.jit(lambda p, prompt, plen, pools, row: G.paged_prefill(
        p, prompt, plen, pools, row, cfg, return_moe=True), donate_argnums=(3,)).lower(
        params, prompt, plen, pools, row).compile()
    text = c.as_text()
    assert _hc_calls(text) == {"pre": 12, "post": 12}
    assert len(re.findall(r"%pfx_grouped_matmul\S* = ", text)) == 15
    assert "ragged-dot" not in text and "ragged_dot" not in text
    m = c.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 13.5e9, m.temp_size_in_bytes
