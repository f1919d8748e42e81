"""Distributed batch sampling + host batch assembly.

Reference: GPTBatchSampler (ppfleetx/data/sampler/batch_sampler.py:31-192) —
slices the global batch across the data-parallel world (dp × sharding ranks,
env.py:158-178) with ``consumed_samples`` resume support.

TPU-native difference: with pjit we assemble the *global* batch on host and
let ``jax.make_array_from_process_local_data`` scatter it; on a single host
the "rank slicing" is purely logical.  The sampler therefore yields global
batches of indices, and resume is a sample counter — the same contract the
reference's checkpoint meta carries.

Iterator-state contract (docs/data_pipeline.md): every loader in this module
exposes ``state_dict()`` / ``load_state(state)`` / ``rewind(consumed_samples)``
— the engine saves the stream position in checkpoint meta, and anomaly
rollback rewinds the stream to the checkpoint position so the replayed data
is token-for-token identical to what an uninterrupted run would have served.
Rewinding invalidates any LIVE iteration (the position is read at
``iter()`` time): callers must re-``iter()`` after a rewind; the loaders'
``rewind`` tears down their background machinery (prefetch thread, worker
pool) so the stale lookahead cannot leak into the replay.
"""

from __future__ import annotations

import time
from typing import Dict, Iterator, List, Sequence

import numpy as np

from paddlefleetx_tpu.utils.log import logger
from paddlefleetx_tpu.utils.registry import SAMPLERS


@SAMPLERS.register("GPTBatchSampler")
class DistributedBatchSampler:
    def __init__(
        self,
        dataset_len: int,
        batch_size: int,
        shuffle: bool = False,
        drop_last: bool = True,
        seed: int = 1234,
        consumed_samples: int = 0,
    ):
        self.n = int(dataset_len)
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.seed = seed
        self.consumed_samples = int(consumed_samples)
        if self.batch_size <= 0:
            raise ValueError(f"batch_size must be positive, got {self.batch_size}")
        if self.drop_last and self.n < self.batch_size:
            # the epoch loop would otherwise spin forever yielding nothing
            # (observed as a silent eval hang on a 4-sample eval split)
            raise ValueError(
                f"dataset has {self.n} samples < batch_size {self.batch_size} "
                "with drop_last: no batch can ever be formed — lower the "
                "batch size (Global.eval_batch_size for eval) or grow the data"
            )

    def __iter__(self) -> Iterator[np.ndarray]:
        epoch = self.consumed_samples // self.n
        offset = self.consumed_samples % self.n
        while True:
            if self.shuffle:
                order = np.random.default_rng(self.seed + epoch).permutation(self.n)
            else:
                order = np.arange(self.n)
            for i in range(offset, self.n - self.batch_size + 1, self.batch_size):
                batch = order[i : i + self.batch_size]
                self.consumed_samples += len(batch)
                yield batch
            if not self.drop_last and (self.n - offset) % self.batch_size:
                tail = order[self.n - (self.n - offset) % self.batch_size :]
                self.consumed_samples += len(tail)
                yield tail
            epoch += 1
            offset = 0

    # -- iterator-state contract ---------------------------------------
    def state_dict(self) -> Dict[str, int]:
        return {"consumed_samples": self.consumed_samples}

    def load_state(self, state: Dict[str, int]) -> None:
        self.rewind(int(state["consumed_samples"]))

    def rewind(self, consumed_samples: int) -> None:
        """Reposition the stream at ``consumed_samples``.  The position is
        read at ``iter()`` time, so a LIVE iterator is unaffected — callers
        must re-``iter()`` (the loaders' ``rewind`` handles this)."""
        cs = int(consumed_samples)
        if cs < 0:
            raise ValueError(f"consumed_samples must be >= 0, got {cs}")
        self.consumed_samples = cs


def collate_stack(items: Sequence[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    """gpt_collate_fn analogue (reference batch_collate_fn.py:95: Tuple of
    Stack over tokens/position_ids/labels/loss_mask) — dict-of-stacked-arrays."""
    keys = items[0].keys()
    return {k: np.stack([it[k] for it in items]) for k in keys}


class DataLoader:
    """Minimal host data loader: sampler indices -> collated numpy batches.

    (The reference uses paddle.io.DataLoader worker processes; token datasets
    here are mmap reads + concatenation — cheap enough to do inline, and the
    engine overlaps host assembly with device steps via async dispatch.)

    Corrupt-sample quarantine: a sample whose fetch/decode raises is skipped
    under a bounded ``max_skips`` budget (``Data.<mode>.loader.max_skips``,
    default 0 = fail on the first bad sample).  The skip substitutes the
    next dataset index deterministically — so a rewound/resumed replay that
    hits the same corrupt record serves the same substitute and the stream
    stays reproducible — records a structured ``data_skip`` event (drained
    into the metrics stream by the engine), and fails loudly naming the
    budget once it is exhausted.  PFX_FAULT sites ``corrupt_sample`` and
    ``io_stall`` fire inside the fetch, keyed by a monotonic per-loader
    fetch counter.
    """

    def __init__(self, dataset, sampler: DistributedBatchSampler,
                 collate_fn=collate_stack, max_skips: int = 0):
        self.dataset = dataset
        self.sampler = sampler
        self.collate_fn = collate_fn
        self.max_skips = int(max_skips)
        self.skips = 0
        # structured data_skip events, appended here and drained by the
        # engine into the metrics stream (decoupled: the loader knows
        # nothing about metrics files)
        self.skip_events: List[Dict] = []
        self._fetch_count = 0
        # (stream_pos, cumulative_skips) per skip, on top of _skip_base
        # (skips restored from a checkpoint).  Lets ``skips_at(pos)`` report
        # the budget spent on TRAINED data only: with prefetch the live
        # ``skips`` counter runs ahead by the lookahead, and saving it
        # would double-charge the budget when the resumed replay re-hits a
        # corrupt sample in the buffered-but-untrained window.
        self._skip_base = 0
        self._skip_log: List[tuple] = []

    def _fetch(self, idx: int):
        from paddlefleetx_tpu.utils import resilience

        self._fetch_count += 1
        resilience.maybe_fire("io_stall", self._fetch_count)
        resilience.maybe_fire("corrupt_sample", self._fetch_count)
        return self.dataset[int(idx)]

    def _get(self, idx: int):
        try:
            return self._fetch(idx)
        except Exception as e:  # noqa: BLE001 — budgeted + re-raised below
            return self._skip_and_substitute(int(idx), e)

    def _budget_error(self, idx: int, err: Exception) -> RuntimeError:
        return RuntimeError(
            f"data.max_skips budget exhausted: sample {idx} failed "
            f"({type(err).__name__}: {err}) after {self.skips} "
            f"skip(s) already spent (data.max_skips={self.max_skips}) — "
            "the data is rotten beyond the configured tolerance; fix the "
            "shard or raise Data.<mode>.loader.max_skips"
        )

    def _skip_and_substitute(self, idx: int, err: Exception):
        if self.skips >= self.max_skips:
            # checked before len(): the budget error must fire even for
            # datasets that cannot offer a substitute
            raise self._budget_error(idx, err) from err
        n = len(self.dataset)
        bad = idx
        for attempt in range(1, max(n, 2)):
            if self.skips >= self.max_skips:
                raise self._budget_error(bad, err) from err
            self.skips += 1
            # the sampler increments consumed_samples BEFORE yielding the
            # batch, so its live counter is this batch's END position
            pos = self.sampler.consumed_samples
            self._skip_log.append((pos, self.skips))
            sub = (idx + attempt) % n  # deterministic: replays substitute
            event = {
                "event": "data_skip",
                "index": bad,
                "substitute": sub,
                "pos": pos,
                "error": f"{type(err).__name__}: {err}",
                "skips": self.skips,
                "max_skips": self.max_skips,
            }
            self.skip_events.append(event)
            logger.error(
                f"DATA SKIP {self.skips}/{self.max_skips}: sample {bad} "
                f"failed ({type(err).__name__}: {err}); substituting "
                f"sample {sub}"
            )
            try:
                return self._fetch(sub)
            except Exception as e:  # noqa: PERF203 — bounded by the budget
                bad, err = sub, e
        raise RuntimeError(
            f"every substitute sample failed after {self.skips} skip(s); "
            f"last error on sample {bad}: {err}"
        ) from err

    def __iter__(self):
        for batch_idx in self.sampler:
            yield self.collate_fn([self._get(int(i)) for i in batch_idx])

    # -- iterator-state contract ---------------------------------------
    def state_dict(self) -> Dict[str, int]:
        state = dict(self.sampler.state_dict())
        state["skips"] = self.skips
        return state

    def load_state(self, state: Dict[str, int]) -> None:
        self.sampler.load_state(state)
        self.skips = int(state.get("skips", self.skips))
        # the restored count is pre-history; the replayed window re-logs
        # its own skips from here
        self._skip_base = self.skips
        self._skip_log = []

    def rewind(self, consumed_samples: int) -> None:
        self.sampler.rewind(consumed_samples)

    def skips_at(self, consumed_samples: int) -> int:
        """Cumulative skips charged by batches at stream positions <=
        ``consumed_samples`` — the value a checkpoint at that position
        must record (the live ``skips`` counter includes prefetched-but-
        untrained batches whose replay will re-spend the budget)."""
        cs = int(consumed_samples)
        out = self._skip_base
        for pos, cum in self._skip_log:
            if pos <= cs:
                out = cum
        return out

    def close(self) -> None:
        """No background machinery to reclaim; present so callers can close
        any loader uniformly."""

    def stats(self) -> Dict[str, float]:
        return {"skips": self.skips}


class WorkerLoader:
    """Worker-process loader: the reference paddle.io.DataLoader
    ``num_workers`` analogue for decode-heavy datasets (image resize /
    augmentation dominate host time for the vision families).

    Workers use the ``spawn`` start method: the training process has live
    XLA/jax threads, and forking a threaded process can deadlock the
    child.  The dataset is pickled once into each worker at pool start
    (datasets and their transform pipelines are plain picklable objects),
    after which only indices and samples cross the pipe.  Nothing a
    worker imports to unpickle a dataset initializes a jax backend
    (tests/test_device_platform.py holds the ``data`` package to that): a
    worker that did would take the chip from the trainer that owns it.  Worker startup costs a fresh interpreter; the
    pool lives for the whole epoch-looping iteration, so this is paid
    once per fit, not per batch.  Sample RNG streams stay deterministic per (seed, idx, visit)
    — but visit counters live per worker, so augmentation draws across
    epochs differ from the single-process order (same guarantee the
    reference's worker processes give).

    Worker exceptions PROPAGATE to the training loop (pool.map re-raises
    in the parent) instead of wedging it; ``close()`` tears down the pool
    so exits are clean.  The corrupt-sample skip budget is an inline
    DataLoader feature — a bad sample here fails loudly (the visit
    counters make silent substitution nondeterministic across worker
    scheduling).  ``rewind`` repositions the sampler but does NOT rewind
    the per-sample visit counters: replayed augmenting samples draw their
    next augmentation, not a byte-identical repeat.
    """

    def __init__(self, dataset, sampler: DistributedBatchSampler,
                 collate_fn=collate_stack, num_workers: int = 2):
        import inspect

        self.dataset = dataset
        self.sampler = sampler
        self.collate_fn = collate_fn
        self.num_workers = max(1, int(num_workers))
        # augmenting datasets key their RNG on (seed, idx, visit); the
        # visit counter must live HERE in the parent — per-worker counters
        # would make draws depend on which worker happened to serve a
        # sample (nondeterministic run-to-run, and epoch 2 frequently
        # replays epoch 1's draw when the sample lands on a fresh worker)
        self._visit_aware = "visit" in inspect.signature(
            dataset.__getitem__
        ).parameters
        self._visits: dict = {}
        self._gen = None

    def _visit(self, idx: int) -> int:
        v = self._visits.get(idx, 0)
        self._visits[idx] = v + 1
        return v

    def _iterate(self):
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        with ctx.Pool(
            self.num_workers, initializer=_worker_init, initargs=(self.dataset,)
        ) as pool:
            for batch_idx in self.sampler:
                if self._visit_aware:
                    work = [(int(i), self._visit(int(i))) for i in batch_idx]
                    items = pool.starmap(
                        _worker_get_visit, work,
                        chunksize=max(1, len(work) // self.num_workers),
                    )
                else:
                    items = pool.map(
                        _worker_get, [int(i) for i in batch_idx],
                        chunksize=max(1, len(batch_idx) // self.num_workers),
                    )
                yield self.collate_fn(items)

    def __iter__(self):
        self.close()  # at most one live pool per loader
        self._gen = self._iterate()
        return self._gen

    # -- iterator-state contract ---------------------------------------
    def state_dict(self) -> Dict[str, int]:
        return self.sampler.state_dict()

    def load_state(self, state: Dict[str, int]) -> None:
        self.close()
        self.sampler.load_state(state)

    def rewind(self, consumed_samples: int) -> None:
        self.close()
        self.sampler.rewind(consumed_samples)

    def close(self) -> None:
        """Terminate the worker pool (GeneratorExit unwinds the ``with
        ctx.Pool`` block) so no worker processes outlive the loader."""
        gen, self._gen = self._gen, None
        if gen is not None:
            gen.close()

    def stats(self) -> Dict[str, float]:
        return {}


_WORKER_DATASET = None


def _worker_init(dataset):
    global _WORKER_DATASET
    _WORKER_DATASET = dataset


def _worker_get(idx: int):
    return _WORKER_DATASET[idx]


def _worker_get_visit(idx: int, visit: int):
    return _WORKER_DATASET.__getitem__(idx, visit)


class _PrefetchIterator:
    """One live prefetch stream: a background thread fills a bounded queue
    from the wrapped loader; the consumer pops with starvation accounting.
    Owned by PrefetchLoader — ``close()`` stops and JOINS the thread."""

    def __init__(self, parent: "PrefetchLoader"):
        import queue
        import threading

        self.parent = parent
        self.q: "queue.Queue" = queue.Queue(maxsize=max(1, parent.depth))
        self.stop = threading.Event()
        self.err: List[BaseException] = []
        self.done = False
        self.thread = threading.Thread(
            target=self._producer, daemon=True, name="pfx-prefetch"
        )
        self.thread.start()

    def _put(self, item) -> bool:
        import queue

        while not self.stop.is_set():
            try:
                self.q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _producer(self):
        try:
            for item in self.parent.loader:
                if not self._put(item):
                    return  # consumer gone: drop buffers, exit thread
        except BaseException as e:  # surface in consumer thread
            self.err.append(e)
        finally:
            self._put(PrefetchLoader._DONE)

    def __iter__(self):
        return self

    def __next__(self):
        import queue

        if self.done:
            raise StopIteration
        t0 = time.monotonic()
        warned = False
        while True:
            try:
                item = self.q.get(timeout=0.5)
                break
            except queue.Empty:
                waited = time.monotonic() - t0
                warn_s = self.parent.stall_warn_s
                if not warned and warn_s > 0 and waited >= warn_s:
                    # step-starvation watchdog: the device is idle waiting
                    # for host data — an I/O stall, slow storage, or an
                    # underpowered host pipeline; warn ONCE per batch
                    warned = True
                    self.parent.stall_warnings += 1
                    logger.warning(
                        f"prefetch starved: training step has waited "
                        f"{waited:.1f}s for the next batch (warn threshold "
                        f"{warn_s:.1f}s) — I/O stall or the host data "
                        "pipeline cannot keep up with the device step"
                    )
        self.parent.data_wait_s += time.monotonic() - t0
        if item is PrefetchLoader._DONE:
            self.done = True
            self._join()
            if self.err:
                raise self.err[0]
            raise StopIteration
        return item

    def depth(self) -> int:
        return self.q.qsize()

    def close(self) -> None:
        self.stop.set()
        self._join()

    def _join(self) -> None:
        self.thread.join(self.parent.join_timeout_s)
        if self.thread.is_alive():
            # blocked inside a dataset fetch (hung storage read): the
            # thread is daemon so the interpreter can still exit, but say
            # so loudly — a clean close should never hit this
            logger.warning(
                f"prefetch thread did not exit within "
                f"{self.parent.join_timeout_s:.1f}s (blocked in a sample "
                "fetch?); leaving the daemon thread behind"
            )


class PrefetchLoader:
    """Background-thread prefetch over any batch iterable (reference
    paddle.io.DataLoader worker analogue): host batch assembly overlaps the
    device step instead of serializing after it.  ``depth`` bounds buffered
    batches (memory = depth x batch bytes).

    Robustness contract: producer exceptions re-raise in the consumer;
    ``stats()`` reports the live queue depth and cumulative ``data_wait_s``
    (consumer seconds spent starved); waits past ``stall_warn_s`` trip a
    loud step-starvation warning; ``close()`` stops AND JOINS the thread so
    exits are clean; ``rewind``/``load_state`` tear down the live stream
    first (its buffered lookahead belongs to the abandoned position).
    """

    _DONE = object()

    def __init__(self, loader, depth: int = 2, stall_warn_s: float = 30.0,
                 join_timeout_s: float = 5.0):
        self.loader = loader
        self.depth = int(depth)
        self.stall_warn_s = float(stall_warn_s)
        self.join_timeout_s = float(join_timeout_s)
        self.data_wait_s = 0.0
        self.stall_warnings = 0
        self._it: "_PrefetchIterator | None" = None

    def __iter__(self):
        self._stop_stream()  # at most one live prefetch thread per loader
        self._it = _PrefetchIterator(self)
        return self._it

    def _stop_stream(self) -> None:
        """Stop and join the live prefetch iterator WITHOUT touching the
        wrapped loader (re-``iter()`` and rewind/load_state restart the
        stream; a plain-generator loader must survive the reset)."""
        it, self._it = self._it, None
        if it is not None:
            it.close()

    def close(self) -> None:
        self._stop_stream()
        # cascade: a wrapped WorkerLoader's spawn pool must not outlive
        # this loader (the producer thread is joined first so it cannot
        # race a live pool.map against the teardown)
        inner = getattr(self.loader, "close", None)
        if callable(inner):
            inner()

    def skips_at(self, consumed_samples: int):
        inner = getattr(self.loader, "skips_at", None)
        return inner(consumed_samples) if callable(inner) else None

    def stats(self) -> Dict[str, float]:
        inner = getattr(self.loader, "stats", None)
        out: Dict[str, float] = dict(inner()) if callable(inner) else {}
        out["data_wait_s"] = round(self.data_wait_s, 3)
        out["prefetch_depth"] = self._it.depth() if self._it is not None else 0
        out["stall_warnings"] = self.stall_warnings
        # mirror onto the process-wide telemetry registry (/metrics):
        # stats() runs at the engine's logging cadence, never per batch,
        # so this is off the hot path; cumulative values are exporter-set
        from paddlefleetx_tpu.utils.telemetry import get_registry

        reg = get_registry()
        reg.counter("pfx_data_wait_seconds_total").set(out["data_wait_s"])
        reg.gauge("pfx_data_prefetch_depth").set(out["prefetch_depth"])
        reg.counter("pfx_data_stall_warnings_total").set(out["stall_warnings"])
        if "skips" in out:
            reg.counter("pfx_data_skips_total").set(out["skips"])
        return out

    # -- iterator-state contract (delegates to the wrapped loader) ------
    def state_dict(self) -> Dict[str, int]:
        return self.loader.state_dict()

    def load_state(self, state: Dict[str, int]) -> None:
        self._stop_stream()
        self.loader.load_state(state)

    def rewind(self, consumed_samples: int) -> None:
        self._stop_stream()
        self.loader.rewind(consumed_samples)

    # skip accounting surfaces through the wrapper so the engine sees one
    # uniform loader interface regardless of the prefetch layer
    @property
    def skips(self) -> int:
        return getattr(self.loader, "skips", 0)

    @property
    def skip_events(self) -> List[Dict]:
        return getattr(self.loader, "skip_events", [])
