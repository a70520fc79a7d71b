"""FLOPs profiler.

TPU-native analogue of the reference flops profiler
(``deepspeed/profiling/flops_profiler/profiler.py:23`` — module-hook counters
patched over torch functional calls). Under XLA none of that machinery is
needed: the compiler already knows the exact op costs of the compiled
program, exposed through ``compiled.cost_analysis()``; per-module analytic
breakdowns come from ``flax.linen.tabulate``. So this profiler has two
sources:

- **compiled**: ``profile_compiled(fn, *args)`` lowers + compiles and reads
  XLA's cost analysis (true executed FLOPs, including rematerialization —
  the number that explains step time).
- **analytic**: ``get_model_profile(model, input_shape)`` — reference-parity
  standalone API returning (flops, macs, params) for one forward pass, with
  an optional per-module table.

Engine integration: with ``flops_profiler.enabled``, the engine profiles its
compiled train step at ``profile_step`` and logs achieved TFLOP/s vs the
accelerator peak.
"""

import jax
import numpy as np

from ...utils.logging import logger, log_dist


def _cost_analysis(compiled):
    return dict(compiled.cost_analysis() or {})


def profile_compiled(fn, *args, full_compile=False, **kwargs):
    """Cost analysis of ``fn`` on these args.

    Default path reads the analysis from the *lowering* (pre-optimization
    StableHLO) — tracing only, no XLA compile, so profiling a step the engine
    already compiled does not pay a second multi-minute compilation at 10B+
    scale. ``full_compile=True`` additionally compiles and reports the
    post-optimization numbers plus program memory. Returns
    ``{"flops", "bytes_accessed"[, "peak_memory"]}``."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    lowered = jitted.lower(*args, **kwargs)
    if not full_compile:
        try:
            ca = dict(lowered.cost_analysis() or {})
            if ca.get("flops"):
                return {
                    "flops": float(ca.get("flops", 0.0)),
                    "bytes_accessed": float(ca.get("bytes accessed", ca.get("bytes_accessed", 0.0))),
                }
        except Exception:
            pass  # fall through to the compiled path
    compiled = lowered.compile()
    ca = _cost_analysis(compiled)
    out = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", ca.get("bytes_accessed", 0.0))),
    }
    try:
        mem = compiled.memory_analysis()
        out["peak_memory"] = float(getattr(mem, "temp_size_in_bytes", 0) +
                                   getattr(mem, "argument_size_in_bytes", 0))
    except Exception:
        pass
    return out


class FlopsProfiler:
    """Profiles a DeepSpeedEngine's compiled train step (reference
    ``FlopsProfiler`` object API: start/stop/get_total_*/print)."""

    def __init__(self, model=None, ds_engine=None):
        self.model = model if model is not None else getattr(ds_engine, "module", None)
        self.engine = ds_engine
        self.started = False
        self._stats = {}
        self._steps = 0
        self._t0 = None

    def start_profile(self, ignore_list=None):
        import time
        self.started = True
        self._steps = 0
        self._t0 = time.perf_counter()
        if self.engine is not None and "train_batch" in self.engine._compiled:
            fn = self.engine._compiled["train_batch"]
            # AOT-compiled steps carry their cost analysis; fall back to 0s
            try:
                self._stats = profile_compiled(fn, self.engine.state, None)
            except Exception:
                self._stats = {}

    def record_step(self, compiled_stats=None):
        self._steps += 1
        if compiled_stats:
            self._stats = compiled_stats

    def stop_profile(self):
        import time
        if self._t0 is not None:
            self._stats["duration"] = time.perf_counter() - self._t0
        self.started = False

    def get_total_flops(self, as_string=False):
        f = self._stats.get("flops", 0.0) * max(self._steps, 1)
        return number_to_string(f, "FLOPs") if as_string else f

    def get_total_macs(self, as_string=False):
        m = self.get_total_flops() / 2
        return number_to_string(m, "MACs") if as_string else m

    def get_total_duration(self, as_string=False):
        d = self._stats.get("duration", 0.0)
        return f"{d:.2f} s" if as_string else d

    def get_total_params(self, as_string=False):
        if self.engine is not None:
            n = sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(self.engine.state.params))
        elif hasattr(self.model, "cfg") and hasattr(self.model.cfg, "num_params"):
            n = self.model.cfg.num_params()
        else:
            n = 0
        return number_to_string(n, "") if as_string else int(n)

    def print_model_profile(self, profile_step=1, module_depth=-1, top_modules=1, detailed=True,
                            output_file=None):
        lines = ["-" * 72, "DeepSpeed-TPU Flops Profiler (XLA cost analysis)", "-" * 72]
        lines.append(f"params:                 {self.get_total_params(as_string=True)}")
        lines.append(f"flops per step:         {number_to_string(self._stats.get('flops', 0), 'FLOPs')}")
        lines.append(f"bytes accessed/step:    {number_to_string(self._stats.get('bytes_accessed', 0), 'B')}")
        if "peak_memory" in self._stats:
            lines.append(f"program memory:         {number_to_string(self._stats['peak_memory'], 'B')}")
        if self._stats.get("duration") and self._steps:
            per_step = self._stats["duration"] / self._steps
            lines.append(f"measured ms/step:       {per_step * 1000:.1f}")
            lines.append(f"achieved TFLOP/s:       {self._stats.get('flops', 0) / per_step / 1e12:.2f}")
        if detailed and hasattr(self.model, "module"):
            try:
                lines.append(module_profile_tree(self.model, depth=module_depth,
                                                 top_modules=top_modules))
            except Exception as e:
                lines.append(f"(per-module table unavailable: {e})")
        report = "\n".join(lines)
        if output_file:
            with open(output_file, "w") as f:
                f.write(report)
        else:
            logger.info("\n" + report)
        return report


def module_profile_tree(model, batch_size=1, seq_len=None, depth=-1, top_modules=3):
    """Reference-style per-module breakdown (``profiler.py:239``
    ``print_model_profile`` depth/top-k tree): analytic forward FLOPs, MACs
    and params per named module scope, aggregated per depth with the top-k
    heaviest modules at each level and their share of the model total.

    Where the reference counts through torch module hooks, here flax's module
    tracer supplies per-scope flops and variables — same tree, no hooks."""
    import jax.numpy as jnp
    from flax.linen import summary

    cfg = model.cfg
    T = seq_len or min(cfg.max_seq_len, 512)
    ids = jnp.zeros((batch_size, T), jnp.int32)
    table = summary._get_module_table(model.module, depth=None, show_repeated=False,
                                      compute_flops=True, compute_vjp_flops=False)(
        {"params": jax.random.key(0)}, ids)

    def row_params(row):
        if not row.counted_variables:
            return 0
        import jax as _jax
        return sum(int(np.prod(v.shape)) for col in row.counted_variables.values()
                   for v in _jax.tree_util.tree_leaves(col))

    raw = [(row.path, type(row.module_copy).__name__,
            float(row.flops) if row.flops not in (None, ) else 0.0, row_params(row))
           for row in table]
    # aggregate params over descendants (flax counts each variable once, at
    # its owning leaf scope)
    rows = [(p, cls, f, sum(pr2 for p2, _, _, pr2 in raw if p2[:len(p)] == p))
            for p, cls, f, _ in raw]
    total_flops = next((f for p, _, f, _ in rows if p == ()), 0.0) or 1.0
    total_params = next((pr for p, _, _, pr in rows if p == ()), 0)
    max_depth = max((len(p) for p, _, _, _ in rows), default=0)
    if depth is None or depth < 0:
        depth = min(max_depth, 3)

    lines = [f"per-module forward profile (bs={batch_size}, seq={T}; "
             f"total {number_to_string(total_flops, 'FLOPs')}, "
             f"{number_to_string(total_params, 'params')}):"]
    for d in range(1, depth + 1):
        level = [(p, cls, f, pr) for p, cls, f, pr in rows if len(p) == d]
        if not level:
            break
        level.sort(key=lambda r: -r[2])
        lines.append(f"depth {d} (top {min(top_modules, len(level))} of {len(level)} modules "
                     f"by fwd FLOPs):")
        for p, cls, f, pr in level[:top_modules]:
            name = "/".join(p)
            lines.append(f"  {name:<34s} {cls:<16s} "
                         f"{number_to_string(pr, 'params'):>14s} "
                         f"{number_to_string(f / 2, 'MACs'):>12s} {100 * f / total_flops:5.1f}%")
    return "\n".join(lines)


def get_model_profile(model, input_shape=None, args=None, print_profile=True, detailed=True,
                      module_depth=-1, top_modules=1, as_string=True, output_file=None,
                      ignore_modules=None, batch=None):
    """Standalone forward-pass profile (reference ``get_model_profile``):
    returns (flops, macs, params) for one forward on ``input_shape`` =
    (batch, seq) token ids, computed by compiling the forward with XLA and
    reading its cost analysis."""
    import jax.numpy as jnp

    if batch is None:
        if input_shape is None:
            raise ValueError("provide input_shape=(batch, seq) or a batch dict")
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, model.cfg.vocab_size, input_shape).astype(np.int32)}
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    params = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), params)

    def fwd(p, ids):
        return model.apply(p, ids)

    stats = profile_compiled(fwd, params, batch["input_ids"])
    flops = stats["flops"]
    macs = flops / 2
    n_params = model.cfg.num_params() if hasattr(model.cfg, "num_params") else 0

    if print_profile:
        log_dist(f"get_model_profile: flops={number_to_string(flops, 'FLOPs')} "
                 f"macs={number_to_string(macs, 'MACs')} params={number_to_string(n_params, '')}", [0])
    if as_string:
        return (number_to_string(flops, "FLOPs"), number_to_string(macs, "MACs"),
                number_to_string(n_params, ""))
    return flops, macs, n_params


def number_to_string(num, unit):
    for scale, prefix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(num) >= scale:
            return f"{num / scale:.2f} {prefix}{unit}"
    return f"{num:.0f} {unit}"
