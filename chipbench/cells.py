"""Finding a cell's files by name, and the checks made before anything runs.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own under this directory, found by the name that
``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: a model configuration;
- ``workloads/<cell>.json``: a cell (its config, its job, its parameters);
- ``metrics/<metric>.json``: a per-layer metric (layer, unit, a reducer by
  name with its arguments), or ``metrics/<metric>.py`` with ``reduce(obs)``;
- ``jobs/<job>.py``: the driver of one kind of job, with ``run(ctx)``.

A later PR adds files and entries and edits none.
"""

import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

class CellError(Exception):
    """The cell cannot run here: the command exits non-zero, no result line."""


def _load(path):
    with open(path) as f:
        return json.load(f)


def end_to_end_units():
    """{metric: unit} of the end-to-end metrics, as ``BENCHMARK.json`` at the
    root of the checkout lists them: a new one is a new entry there and a
    number under that name in a job's ``end_to_end``."""
    bench = _load(os.path.join(os.path.dirname(HERE), "BENCHMARK.json"))
    return {m["name"]: m["unit"] for m in bench["end_to_end"]}


def load_peaks():
    return _load(os.path.join(HERE, "peaks.json"))


def load_workload(name):
    """``name`` is a cell of ``workloads/``, or the path of a rehearsal
    workload ``<root>/workloads/<cell>.json`` (``tests/fixtures`` is such a
    root, with its own ``configs/`` and ``metrics/``). Returns (cell name,
    workload dict, root directory)."""
    if name.endswith(".json"):
        path = os.path.abspath(name)
        root = os.path.dirname(os.path.dirname(path))
        wl = _load(path)
        if not wl.get("rehearsal"):
            raise CellError(f"{name}: a workload given as a path must be marked "
                            f"as a rehearsal")
        return os.path.basename(path)[:-len(".json")], wl, root
    path = os.path.join(HERE, "workloads", name + ".json")
    if not os.path.exists(path):
        raise CellError(f"no workload file {os.path.relpath(path)}")
    wl = _load(path)
    if wl.get("rehearsal"):
        raise CellError(f"{name}: a cell under workloads/ cannot be a rehearsal")
    return name, wl, HERE


def load_config(name, root=HERE):
    path = os.path.join(root, "configs", name + ".json")
    if not os.path.exists(path):
        raise CellError(f"no configuration file {os.path.relpath(path)}")
    return _load(path)


def build_model(config, **model_overrides):
    """The program's model for a configuration file, checked against the
    sizes the file says it runs (``expect``)."""
    from deepspeed_tpu.models import get_model
    model = get_model(config["preset"], **config.get("overrides", {}), **model_overrides)
    for key, want in config.get("expect", {}).items():
        got = getattr(model.cfg, key)
        if got != want:
            raise CellError(f"configuration {config['name']}: the program builds {key}="
                            f"{got!r}, the file says {want!r}")
    return model


def per_layer_metrics(cell, workload, root=HERE):
    """{metric name: definition} of the per-layer metrics a cell reports: the
    metric files that list the cell (or list no cells), and those the
    workload file names itself. A new cell never edits a metric's file and a
    new metric never edits a cell's."""
    found = {}
    for d in dict.fromkeys([os.path.join(HERE, "metrics"), os.path.join(root, "metrics")]):
        if os.path.isdir(d):
            for fn in sorted(os.listdir(d)):
                if fn.endswith(".json"):
                    found[fn[:-5]] = dict(_load(os.path.join(d, fn)), name=fn[:-5], dir=d)
    wanted = set(workload.get("per_layer", ()))
    out = {name: m for name, m in found.items()
           if name in wanted or ("workloads" in m and cell in m["workloads"])
           or ("workloads" not in m and not workload.get("rehearsal"))}
    missing = wanted - set(out)
    if missing:
        raise CellError(f"{cell}: names per-layer metrics with no file: {sorted(missing)}")
    return out


def load_job(name):
    try:
        return importlib.import_module(f"chipbench.jobs.{name}")
    except ModuleNotFoundError as e:
        if e.name == f"chipbench.jobs.{name}":
            raise CellError(f"no job driver chipbench/jobs/{name}.py") from e
        raise


def custom_reducer(metric):
    """``metrics/<metric>.py``, beside the metric's ``.json``, if the metric
    brings its own reader."""
    metric_name = metric["name"]
    path = os.path.join(metric["dir"], metric_name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{metric_name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def claim_devices(chips, rehearsal):
    """The devices the cell runs on, and the ``device`` block of the result.
    No accelerator, too few chips or an unknown ``device_kind`` is an error:
    there is no CPU fallback outside a rehearsal fixture."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if rehearsal:
        if dev.platform != "cpu":
            raise CellError("a rehearsal fixture runs on the CPU only "
                            "(JAX_PLATFORMS=cpu)")
        peaks = None
    else:
        if dev.platform != "tpu":
            raise CellError(f"JAX found no TPU (platform={dev.platform!r}): a cell "
                            f"measures the chip and does not run without one")
        table = load_peaks()
        if dev.device_kind not in table:
            raise CellError(f"device_kind {dev.device_kind!r} is not in chipbench/peaks.json")
        peaks = table[dev.device_kind]
    if len(devices) < chips:
        raise CellError(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    block = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    return devices[:chips], peaks, block


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest of the cell's chips (0 where the
    backend reports none, as the CPU does)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
