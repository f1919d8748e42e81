"""End-to-end engine tests: tiny GPT pretrain on the 8-device CPU mesh —
loss decreases, checkpoint save/load resumes, layouts agree.

This is the TIPC-harness analogue (SURVEY §4): loss-curve + throughput are
the golden signals; here we assert the loss actually drops."""

import os
import pytest

import jax
import numpy as np

from paddlefleetx_tpu.core.engine import Engine
from paddlefleetx_tpu.core.module import build_module
from paddlefleetx_tpu.data.builders import build_dataloader
from paddlefleetx_tpu.data.gpt_dataset import write_synthetic_corpus
from paddlefleetx_tpu.parallel.env import init_dist_env
from paddlefleetx_tpu.utils.config import AttrDict, process_configs


def tiny_cfg(tmp_path, **dist):
    data_dir = str(tmp_path / "data")
    os.makedirs(data_dir, exist_ok=True)
    write_synthetic_corpus(os.path.join(data_dir, "corpus"), vocab_size=128, num_docs=16)
    cfg = AttrDict.from_nested(
        {
            "Global": {"global_batch_size": 16, "micro_batch_size": 1, "seed": 7},
            "Engine": {
                "max_steps": 12,
                "eval_freq": 0,
                "logging_freq": 4,
                "mix_precision": {"enable": False},
                "save_load": {"save_steps": 0, "output_dir": str(tmp_path / "out")},
            },
            "Model": {
                "module": "GPTModule",
                "vocab_size": 128,
                "hidden_size": 64,
                "num_layers": 2,
                "num_attention_heads": 8,
                "max_position_embeddings": 32,
                "hidden_dropout_prob": 0.0,
                "attention_probs_dropout_prob": 0.0,
                "dtype": "float32",
            },
            "Distributed": dist,
            "Data": {
                "Train": {
                    "dataset": {
                        "name": "GPTDataset",
                        "input_dir": data_dir,
                        "max_seq_len": 32,
                        "split": [1, 0, 0],
                    },
                    "sampler": {"shuffle": True},
                },
            },
            "Optimizer": {
                "name": "FusedAdamW",
                "weight_decay": 0.01,
                "lr": {"name": "Constant", "learning_rate": 3e-3},
                "grad_clip": {"name": "ClipGradByGlobalNorm", "clip_norm": 1.0},
            },
        }
    )
    return process_configs(cfg, num_devices=8)


def _losses_from_run(cfg, steps=12):
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    loader = build_dataloader(cfg, "Train")
    with mesh:
        engine = Engine(cfg, module, mesh)
        losses = []
        it = iter(loader)
        for _ in range(steps):
            batch = next(it)
            dev = engine._put_batch(batch)
            engine.state, m = engine.train_step(engine.state, dev)
            losses.append(float(m["loss"]))
    return losses, engine


def test_train_loss_decreases(tmp_path, devices8):
    cfg = tiny_cfg(tmp_path)
    losses, _ = _losses_from_run(cfg)
    assert losses[0] > 4.0  # ~ln(128)=4.85
    assert np.mean(losses[-3:]) < np.mean(losses[:3]) - 0.2


@pytest.mark.slow  # ~18s (six engine boots); tier-1 budget funding for
# the shard_map-port tests that re-opened this test on jax 0.4.37.
# Replacement coverage: cross-layout LOSS parity stays tier-1 via
# test_gpt_model::test_layout_parity (model-level, same layout family),
# and every layout is engine-exercised tier-1 somewhere — pp via the
# zigzag pp2xsep2 worker (Engine.train_step), fsdp via zero-offload,
# sep via the ring suite, dp/mp via serving/TP parity; this exact
# six-layout engine sweep runs in `make test-parallel` / test-mid /
# test-all.
def test_layout_loss_parity_first_step(tmp_path, devices8):
    """Same data+seed, different layouts -> same first-step loss (the
    reference's cross-layout precision-validation contract)."""
    first = {}
    for name, dist in {
        "dp8": {},
        "mp8": {"mp_degree": 8},
        "dp2mp4": {"mp_degree": 4},
        "fsdp": {"sharding": {"sharding_degree": 8, "sharding_stage": 2}},
        "dp2mp2pp2": {"mp_degree": 2, "pp_degree": 2},
        "dp2mp2sep2": {"mp_degree": 2, "sep_degree": 2},
    }.items():
        cfg = tiny_cfg(tmp_path, **dist)
        losses, _ = _losses_from_run(cfg, steps=2)
        first[name] = losses
    base = first["dp8"]
    for name, ls in first.items():
        np.testing.assert_allclose(ls, base, rtol=2e-4, err_msg=name)


@pytest.mark.slow  # ~15s engine boot; the bf16 precision family stays
# tier-1 via test_multi_precision_off_bf16_params_train (the sibling
# bf16 contract) and the fp16 loss-scaling pair; still in make test-mid
# / test-all (PR 8 tier-1 budget convention)
def test_main_grad_off_bf16_grads_train(tmp_path, devices8):
    """mix_precision.main_grad=False (bf16 grads, the 1.3B-fit lever):
    still trains, and tracks the fp32-main-grad bf16 run closely."""
    runs = {}
    for main_grad in (True, False):
        cfg = tiny_cfg(tmp_path)
        cfg.Engine.mix_precision = AttrDict.from_nested(
            {"enable": True, "dtype": "bfloat16", "main_grad": main_grad}
        )
        cfg.Model.dtype = "bfloat16"
        losses, engine = _losses_from_run(cfg, steps=8)
        # params/optimizer masters stay fp32 either way
        assert jax.tree.leaves(engine.state.params)[0].dtype == np.float32
        runs[main_grad] = losses
    # identical first step (loss is computed before any update), close after
    np.testing.assert_allclose(runs[True][0], runs[False][0], rtol=1e-5)
    np.testing.assert_allclose(runs[True], runs[False], rtol=0.05)
    assert np.mean(runs[False][-3:]) < np.mean(runs[False][:3]) - 0.1


def test_abstract_init_memory_report(tmp_path, devices8):
    """Engine(abstract_init=True): no state is allocated (leaves are
    ShapeDtypeStructs) and memory_report returns per-device byte stats
    from the AOT-compiled train step — the 6.7B fit-check path
    (tools/fit_6p7b.py) at tiny dims."""
    import numpy as np_

    cfg = tiny_cfg(tmp_path)
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    with mesh:
        engine = Engine(cfg, module, mesh, abstract_init=True)
        assert all(
            isinstance(x, jax.ShapeDtypeStruct)
            for x in jax.tree.leaves(engine.state.params)
        )
        seq = int(cfg.Model.max_position_embeddings)
        b = int(cfg.Global.global_batch_size)
        stats = engine.memory_report({
            "tokens": ((b, seq), np_.int32),
            "labels": ((b, seq), np_.int32),
            "loss_mask": ((b, seq), np_.float32),
            "position_ids": ((b, seq), np_.int32),
        })
    assert stats["params_bytes_per_device"] > 0
    assert stats["peak_bytes_per_device_est"] >= stats["params_bytes_per_device"]


def test_main_grad_off_requires_amp(tmp_path, devices8):
    """mix_precision.enable=False + main_grad=False is contradictory
    (main_grad only controls the AMP gradient dtype): the engine raises
    instead of silently bf16-casting a nominally-fp32 run (advisor r4)."""
    import pytest

    cfg = tiny_cfg(tmp_path)
    cfg.Engine.mix_precision = AttrDict.from_nested(
        {"enable": False, "main_grad": False}
    )
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    with pytest.raises(ValueError, match="main_grad"):
        with mesh:
            Engine(cfg, module, mesh)


def test_multi_precision_off_bf16_params_train(tmp_path, devices8):
    """Optimizer.multi_precision=False (reference FusedAdamW flag): bf16
    params, no fp32 masters, moments follow — trains, and checkpoint
    roundtrips preserve the dtype."""
    cfg = tiny_cfg(tmp_path)
    cfg.Engine.mix_precision = AttrDict.from_nested(
        {"enable": True, "dtype": "bfloat16"}
    )
    cfg.Model.dtype = "bfloat16"
    cfg.Optimizer.multi_precision = False
    losses, engine = _losses_from_run(cfg, steps=8)
    import jax.numpy as jnp

    leaves = jax.tree.leaves(engine.state.params)
    assert all(x.dtype == jnp.bfloat16 for x in leaves)
    # optax moments follow the param dtype (mu pinned bf16 by moment_dtype
    # anyway; nu now bf16 too — the multi_precision=False memory win)
    assert all(
        x.dtype in (jnp.bfloat16, jnp.int32)
        for x in jax.tree.leaves(engine.state.opt_state)
    )
    assert np.isfinite(losses).all()
    assert np.mean(losses[-3:]) < np.mean(losses[:3]) - 0.1

    path = engine.save(str(tmp_path / "ckpt_mp0"))
    cfg2 = tiny_cfg(tmp_path)
    cfg2.Engine.mix_precision = AttrDict.from_nested(
        {"enable": True, "dtype": "bfloat16"}
    )
    cfg2.Model.dtype = "bfloat16"
    cfg2.Optimizer.multi_precision = False
    mesh = init_dist_env(cfg2)
    module = build_module(cfg2)
    with mesh:
        engine2 = Engine(cfg2, module, mesh)
        engine2.load(path)
        assert jax.tree.leaves(engine2.state.params)[0].dtype == jnp.bfloat16


def test_checkpoint_roundtrip(tmp_path, devices8):
    cfg = tiny_cfg(tmp_path)
    losses, engine = _losses_from_run(cfg, steps=4)
    path = engine.save(str(tmp_path / "ckpt"))

    cfg2 = tiny_cfg(tmp_path)
    mesh = init_dist_env(cfg2)
    module = build_module(cfg2)
    with mesh:
        engine2 = Engine(cfg2, module, mesh)
        engine2.load(path)
        assert int(engine2.state.step) == 4
        for a, b in zip(jax.tree.leaves(engine.state.params), jax.tree.leaves(engine2.state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fit_smoke(tmp_path, devices8, capsys):
    cfg = tiny_cfg(tmp_path)
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    loader = build_dataloader(cfg, "Train")
    with mesh:
        engine = Engine(cfg, module, mesh)
        state = engine.fit(loader)
    assert int(state.step) == 12


# ---------------------------------------------------------------------------
# fp16 parity path: DynamicLossScaler (reference apis/amp.py:193-234)
# ---------------------------------------------------------------------------


def _fp16_cfg(tmp_path, init_scale, incr_every=1000):
    cfg = tiny_cfg(tmp_path)
    cfg.Engine.mix_precision = AttrDict.from_nested(
        {
            "enable": True,
            "dtype": "float16",
            "scale_loss": {
                "init": init_scale,
                "incr_every_n_steps": incr_every,
                "incr_ratio": 2.0,
                "decr_ratio": 0.5,
            },
        }
    )
    cfg.Model.dtype = "float16"
    return cfg


def test_fp16_loss_scaling_trains_and_grows(tmp_path, devices8):
    """fp16 compute + dynamic loss scale: steps are finite, and the scale
    doubles after incr_every consecutive good steps."""
    cfg = _fp16_cfg(tmp_path, init_scale=1024.0, incr_every=2)
    losses, engine = _losses_from_run(cfg, steps=5)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    # 5 good steps with incr_every=2 -> grew twice: 1024 -> 2048 -> 4096
    assert float(engine.state.scaler["scale"]) == 4096.0


def test_fp16_overflow_shrinks_scale_and_skips(tmp_path, devices8):
    """An absurd initial scale overflows fp16 gradients: the step must be
    skipped (params unchanged) and the scale halved (found_inf contract)."""
    import jax.numpy as jnp

    cfg = _fp16_cfg(tmp_path, init_scale=float(2.0**31))
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    loader = build_dataloader(cfg, "Train")
    with mesh:
        engine = Engine(cfg, module, mesh)
        p0 = jax.tree.map(lambda x: np.asarray(x), engine.state.params)
        batch = next(iter(loader))
        dev = engine._put_batch(batch)
        engine.state, m = engine.train_step(engine.state, dev)
    assert float(m["found_inf"]) == 1.0
    assert float(engine.state.scaler["scale"]) == 2.0**30
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(engine.state.params)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_metrics_file_stream(tmp_path, devices8):
    """Engine.metrics_file writes one parseable JSON line per logging step."""
    import json

    cfg = tiny_cfg(tmp_path)
    cfg.Engine.metrics_file = str(tmp_path / "metrics.jsonl")
    cfg.Engine.max_steps = 8
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    loader = build_dataloader(cfg, "Train")
    with mesh:
        engine = Engine(cfg, module, mesh)
        engine.fit(loader)
    lines = [json.loads(x) for x in open(cfg.Engine.metrics_file)]
    assert len(lines) == 2  # logging_freq=4, max_steps=8
    assert {"step", "loss", "lr", "grad_norm", "ips", "consumed_samples"} <= set(lines[0])
    assert lines[-1]["step"] == 8 and np.isfinite(lines[-1]["loss"])
    # training goodput ledger rides every record (docs/observability.md
    # "Goodput ledger"): exhaustive fit-loop buckets, all non-negative,
    # with compile attributed on the record that paid it
    led = lines[-1]["time_ledger"]
    assert set(led) == {"compile", "device_step", "data_wait", "host",
                        "eval"}
    assert all(v >= 0.0 for v in led.values()), led
    assert sum(led.values()) > 0.0, led


def _fake_ckpt(root, step, payload="state", meta=True, metadata=True, data=True):
    """A structurally valid step dir (meta marker + orbax-shaped payload)
    without paying for a real orbax save — see checkpoint.validate_checkpoint.
    The knockout flags build each flavor of broken dir (shared with
    tests/test_fault_tolerance.py)."""
    d = root / f"step_{step}"
    d.mkdir()
    if meta:
        (d / "meta.json").write_text('{"step": %d}' % step)
    if payload:
        (d / payload / "d").mkdir(parents=True)
        if metadata:
            (d / payload / "_METADATA").write_text("{}")
        if data:
            (d / payload / "d" / "data0").write_bytes(b"\x01" * 32)
    return d


def test_latest_checkpoint_selection(tmp_path):
    """latest_checkpoint picks the highest complete step dir and skips
    crash-truncated saves (no meta.json)."""
    from paddlefleetx_tpu.utils.checkpoint import latest_checkpoint

    assert latest_checkpoint(str(tmp_path / "missing")) is None
    for step in (2, 10):
        _fake_ckpt(tmp_path, step)
    (tmp_path / "step_30").mkdir()  # crashed save: no meta.json
    (tmp_path / "step_bogus").mkdir()
    assert latest_checkpoint(str(tmp_path)).endswith("step_10")
    # the in-flight/crashed dir is left alone (an async save from a live
    # process has no meta yet; only meta-complete-but-broken is quarantined)
    assert (tmp_path / "step_30").is_dir()


def test_latest_checkpoint_skips_corrupt_meta(tmp_path):
    """A crash-truncated meta.json must not wedge the restart loop: the
    newest PARSEABLE checkpoint wins."""
    from paddlefleetx_tpu.utils.checkpoint import latest_checkpoint

    _fake_ckpt(tmp_path, 4)
    bad = tmp_path / "step_9"
    bad.mkdir()
    (bad / "meta.json").write_text('{"step": 9')  # truncated write
    assert latest_checkpoint(str(tmp_path)).endswith("step_4")


def test_async_checkpoint_roundtrip(tmp_path, devices8):
    """save_load.async_save: the array write overlaps training; meta.json
    (the completeness marker) lands only once the write is durable, and
    wait_for_save()/load() join the in-flight write."""
    cfg = tiny_cfg(tmp_path)
    cfg.Engine.save_load.async_save = True
    losses, engine = _losses_from_run(cfg, steps=3)
    path = engine.save(str(tmp_path / "ackpt"))
    engine.wait_for_save()
    assert os.path.exists(os.path.join(path, "meta.json"))

    cfg2 = tiny_cfg(tmp_path)
    mesh = init_dist_env(cfg2)
    module = build_module(cfg2)
    with mesh:
        engine2 = Engine(cfg2, module, mesh)
        engine2.load(path)
        assert int(engine2.state.step) == 3
        for a, b in zip(
            jax.tree.leaves(engine.state.params), jax.tree.leaves(engine2.state.params)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # a second async save against the same engine joins the first
    path2 = engine.save(str(tmp_path / "ackpt2"))
    engine.wait_for_save()
    assert os.path.exists(os.path.join(path2, "meta.json"))


def test_async_save_error_surfaces(tmp_path, devices8, monkeypatch):
    """A background write failure must raise at wait_for_save, not vanish
    in the finisher thread (silent checkpoint loss)."""
    cfg = tiny_cfg(tmp_path)
    cfg.Engine.save_load.async_save = True
    _, engine = _losses_from_run(cfg, steps=1)
    path = engine.save(str(tmp_path / "good"))
    engine.wait_for_save()

    # fail the finisher (meta write) — AsyncCheckpointer.save itself calls
    # wait_until_finished, so patching that would raise in save() instead
    def boom(path, meta):
        raise OSError("disk full")

    monkeypatch.setattr(engine, "_write_meta", boom)
    bad = engine.save(str(tmp_path / "bad"))
    import pytest as _pytest

    with _pytest.raises(OSError, match="disk full"):
        engine.wait_for_save()
    # no completeness marker: resume correctly skips the directory
    assert not os.path.exists(os.path.join(bad, "meta.json"))
    assert os.path.exists(os.path.join(path, "meta.json"))


def test_evaluate_empty_loader_raises_loudly(tmp_path, devices8):
    """Satellite (ISSUE 9): evaluate on an empty/exhausted loader used to
    return float('nan') silently; the default now raises, and the in-fit
    spelling (on_empty='event') logs + emits a structured eval_empty
    event instead of poisoning downstream records."""
    import json

    cfg = tiny_cfg(tmp_path)
    cfg.Engine.metrics_file = str(tmp_path / "ev_metrics.jsonl")
    _, engine = _losses_from_run(cfg, steps=1)
    with pytest.raises(RuntimeError, match="ZERO batches"):
        engine.evaluate([], iters=4)
    # event branch: nan returned, but loudly + structured
    val = engine.evaluate([], iters=4, on_empty="event")
    assert val != val  # nan
    events = [json.loads(x) for x in open(cfg.Engine.metrics_file)]
    assert any(e.get("event") == "eval_empty" for e in events)
    with pytest.raises(ValueError, match="on_empty"):
        engine.evaluate([], on_empty="typo")


def test_evaluate_nonempty_still_returns_mean(tmp_path, devices8):
    """The healthy branch: a real loader evaluates to a finite mean."""
    cfg = tiny_cfg(tmp_path)
    mesh = init_dist_env(cfg)
    module = build_module(cfg)
    loader = build_dataloader(cfg, "Train")
    with mesh:
        engine = Engine(cfg, module, mesh)
        val = engine.evaluate(loader, iters=2)
    assert np.isfinite(val)
