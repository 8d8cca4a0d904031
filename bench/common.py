"""What the harness and its engine adapters share: loading the per-name files,
the program's configuration as a configuration file states it, the measured
window, and the device's memory peak."""
from __future__ import annotations

import importlib.util
import re
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def load_module(path: Path):
    """Import one file of the benchmark by its path (names may hold ``-``
    and ``.``, which ``import`` does not take)."""
    path = Path(path)
    name = "bench_" + re.sub(r"\W", "_", str(path.relative_to(ROOT)))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program_config(config):
    """The program's model configuration for a configuration file: the
    registry entry it names with the file's overrides, checked against
    every number the file states under ``config``."""
    from repro.configs.registry import get_config

    prog = config["program"]
    cfg = get_config(prog["registry"]).replace(**prog.get("overrides", {}))
    for k, v in config["config"].items():
        attr = "resolved_head_dim" if k == "head_dim" else k
        if hasattr(cfg, attr) and getattr(cfg, attr) != v:
            raise ValueError(f"{config['name']}: the program runs {k}="
                             f"{getattr(cfg, attr)!r}, the file states {v!r}")
    return cfg


def enable_compile_cache(root=ROOT):
    """JAX's persistent compilation cache at the fixed ``.jax_cache/`` of
    the checkout, every program cached, no eviction: a size limit that the
    environment sets would turn eviction on, whose scan of the directory
    fails on an entry written without an access time."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def memory_peak():
    """Peak bytes in use on the fullest device of this process."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


class Window:
    """The measured window: host-clock bounds and, in a traced run, the
    profiler session, with markers on the trace's host timeline at both
    ends."""

    OPEN, CLOSE = "bench.window.open", "bench.window.close"

    def __init__(self, trace_dir=None):
        self.trace_dir = trace_dir
        self.t0 = self.t1 = None

    def open(self):
        import jax

        if self.trace_dir is not None:
            # the Python tracer off: it would slow the host loops and
            # inflate the device's idle share
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            with jax.profiler.TraceAnnotation(self.OPEN):
                pass
        self.t0 = time.perf_counter()

    def close(self):
        import jax

        self.t1 = time.perf_counter()
        if self.trace_dir is not None:
            with jax.profiler.TraceAnnotation(self.CLOSE):
                pass
            jax.profiler.stop_trace()

    @property
    def seconds(self):
        return self.t1 - self.t0


class Context:
    """One run's inputs: the cell, its configuration and traffic files, the
    seed and window length, and the window object."""

    def __init__(self, *, workload, config, traffic, seed, seconds,
                 window, root=ROOT):
        self.workload = workload
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.window = window
        self.root = root
