"""Shared, stdlib-only helpers of the benchmark: data files found by name,
child processes, HTTP and Prometheus text.  The parent never imports jax
(one process owns a chip at a time), so nothing here does."""

import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import urllib.error
import urllib.request

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
BENCH_REL = os.path.basename(BENCH)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class Fail(Exception):
    """The run cannot give a result: no result line, non-zero exit."""


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts):
    path = os.path.join(BENCH, *parts)
    if not os.path.isfile(path):
        raise Fail(f"no data file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return load_json("configs", f"{name}.json")


def load_traffic(name: str) -> dict:
    return load_json("traffic", f"{name}.json")


def load_cell(name: str) -> dict:
    """A cell with its configuration and traffic mix resolved by name."""
    if not NAME_RE.match(name):
        raise Fail(f"bad workload name {name!r}")
    cell = load_json("workloads", f"{name}.json")
    cell["config_data"] = load_config(cell["config"])
    cell["traffic_data"] = load_traffic(cell["traffic"])
    return cell


def load_layer_metric(name: str) -> dict:
    return load_json("layer_metrics", f"{name}.json")


def load_peaks(device_kind: str) -> dict:
    peaks = load_json("peaks.json")["devices"]
    if device_kind not in peaks:
        raise Fail(f"device kind {device_kind!r} is not in peaks.json: add it "
                   "with its source, there is no default")
    return peaks[device_kind]


def out_dir(cell: str, seed: int, trace: int) -> str:
    """Small outputs of one run; comes back from the chip machine."""
    d = os.path.join(ROOT, "chiprun_out", BENCH_REL, cell, f"seed{seed}-trace{trace}")
    os.makedirs(d, exist_ok=True)
    return d


def work_dir(cell: str) -> str:
    """Large scratch of one run (corpus, trace): inside the checkout,
    git-ignored, overwritten by the next run of the cell."""
    d = os.path.join(ROOT, "output", BENCH_REL, cell)
    os.makedirs(d, exist_ok=True)
    return d


# -- program overrides from data ------------------------------------------


def model_overrides(config: dict, rehearse: bool) -> list:
    """The configuration's sizes as the program's ``-o`` overrides, so the
    yaml decides nothing the configuration file states.  ``--rehearse``
    swaps in the toy widths the file keeps for the CPU self-test."""
    model = config["rehearse_model"] if rehearse else config["model"]
    return [f"Model.{k}={v}" for k, v in model.items()]


def train_overrides(cell: dict, seed: int, rehearse: bool) -> list:
    t = cell["traffic_data"]
    b = t["rehearse"]["global_batch_size"] if rehearse else t["global_batch_size"]
    s = t["rehearse"]["seq_len"] if rehearse else t["seq_len"]
    chips = int(cell["chips"])
    dist = dict(cell.get("distributed", {}))
    dp = chips // (int(dist.get("mp_degree", 1)) * int(dist.get("pp_degree", 1)))
    local = b // max(1, dp)
    out = model_overrides(cell["config_data"], rehearse)
    out += [f"Global.seed={seed % (2 ** 31)}", f"Global.global_batch_size={b}",
            f"Global.local_batch_size={local}", f"Global.micro_batch_size={local}",
            f"Data.Train.dataset.max_seq_len={s}"]
    out += [f"Distributed.{k}={v}" for k, v in dist.items()]
    out += list(cell.get("overrides", []))
    return out


# -- child processes ---------------------------------------------------------


def child_env(rehearse: bool, chips: int) -> dict:
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PFX_PLATFORM"] = "cpu" if rehearse else "tpu"
    env.setdefault("TPU_LOG_DIR", "disabled")
    env["PYTHONUNBUFFERED"] = "1"
    # flight-recorder dumps and on-demand profiles land inside the checkout
    env["PFX_FLIGHT_DIR"] = os.path.join(ROOT, "output", BENCH_REL, "flight")
    if rehearse:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    return env


def stop_child(proc, grace: float = 30.0) -> None:
    """SIGTERM the child's process group, wait, SIGKILL what is left."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)  # stragglers of the group
    except ProcessLookupError:
        pass
    proc.wait()


def run_to_end(argv, env, log_path, timeout):
    """Run one child to its end with its output in ``log_path``; returns
    the exit code (kills the group at ``timeout``)."""
    with open(log_path, "w") as log:
        proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=log,
                                stderr=subprocess.STDOUT, start_new_session=True)
        timer = threading.Timer(timeout, lambda: os.killpg(proc.pid, signal.SIGKILL))
        timer.start()
        try:
            rc = proc.wait()
        finally:
            timer.cancel()
            stop_child(proc, grace=1.0)
    return rc


def tail(path: str, n: int = 30) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


# -- HTTP and Prometheus text (copies of chip_smoke.py's helpers) ------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(port: int, path: str, body=None, timeout: float = 120.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def parse_metrics(text: str) -> dict:
    """Prometheus exposition -> {"name{labels}": value} (labels verbatim)."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        try:
            out[head] = float(val)
        except ValueError:
            continue
    return out


def metric_sum(metrics: dict, name: str, **labels) -> float:
    """Sum of one family's samples whose labels include ``labels``."""
    total = 0.0
    for key, val in metrics.items():
        fam, _, rest = key.partition("{")
        if fam != name:
            continue
        if all(f'{k}="{v}"' in rest for k, v in labels.items()):
            total += val
    return total


def python() -> str:
    return sys.executable or "python3"
