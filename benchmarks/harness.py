"""Machine-readable benchmark harness: every figure's timings as
stable-schema ``BENCH_<name>.json`` records.

Each benchmark section builds one :class:`Bench`, replaces its ad-hoc
prints with :meth:`Bench.record` (stdout keeps the ``name,key,value``
CSV convention), and calls :meth:`Bench.finish` to write
``$BENCH_DIR/BENCH_<name>.json``.  ``BENCH_DIR`` defaults to the
working directory; set it empty (``BENCH_DIR=``) to disable the JSON
side entirely (CSV still prints).

File schema (``schema_version`` = :data:`SCHEMA_VERSION`)::

    {
      "schema_version": 1,
      "name": "fig9",                  # section name
      "created_unix": 1e9,             # write time
      "backend": "cpu", "jax": "0.4.37",
      "records": [
        {
          "key": "compiled/n8",        # unique within the file
          "value": 123.4,              # the CSV value (number if it
                                       #  parses, else string)
          "shape":   {"backend", "n", "d", "devices", "net"},   # opt
          "knobs":   {"chunk", "collective", "block_d", ...},   # opt
          "wall_clock_s": 1.2,         # opt: measured wall time
          "rounds_per_sec": 80.1,      # opt: throughput
          "hlo":     {"flops", "bytes", "collective_bytes",
                      "op_count_total", "collective_counts",
                      "unknown_trip_whiles", "chunk"},          # opt
          "fidelity": {...},           # opt: accuracy/variance/drop
                                       #  columns next to the timings
        }, ...
      ]
    }

``tools/check_bench.py`` compares the deterministic columns (``hlo``)
against committed baselines and treats the wall-clock columns as
warn-only (runner noise).
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional

SCHEMA_VERSION = 1


def _num(value):
    """CSV values are printed pre-formatted; store them as numbers when
    they parse so downstream tooling never re-parses strings."""
    if isinstance(value, (int, float)):
        return value
    try:
        f = float(str(value))
    except (TypeError, ValueError):
        return str(value)
    return int(f) if f.is_integer() and "." not in str(value) \
        and "e" not in str(value).lower() else f


class Bench:
    """Recorder for one benchmark section (see module docstring)."""

    def __init__(self, name: str, out_dir: Optional[str] = None):
        self.name = name
        if out_dir is None:
            out_dir = os.environ.get("BENCH_DIR", ".")
        self.out_dir = out_dir
        self.records: list = []

    # -- emission ----------------------------------------------------------

    def record(self, key, value=None, *, shape: Optional[Dict] = None,
               knobs: Optional[Dict] = None,
               wall_clock_s: Optional[float] = None,
               rounds_per_sec: Optional[float] = None,
               hlo: Optional[Dict] = None,
               fidelity: Optional[Dict] = None,
               print_csv: bool = True, **extra) -> Dict:
        """Store one full-schema record; prints the CSV line for
        ``value`` unless suppressed.  Returns the record dict."""
        rec: Dict = {"key": str(key)}
        if value is not None:
            rec["value"] = _num(value)
            if print_csv:
                print(f"{self.name},{key},{value}", flush=True)
        for field, v in (("shape", shape), ("knobs", knobs),
                         ("wall_clock_s", wall_clock_s),
                         ("rounds_per_sec", rounds_per_sec),
                         ("hlo", hlo), ("fidelity", fidelity)):
            if v is not None:
                rec[field] = v
        rec.update(extra)
        self.records.append(rec)
        return rec

    def finish(self) -> Optional[str]:
        """Write ``BENCH_<name>.json`` (returns its path; None when the
        JSON side is disabled via ``BENCH_DIR=``)."""
        if not self.out_dir:
            return None
        import jax
        payload = {
            "schema_version": SCHEMA_VERSION,
            "name": self.name,
            "created_unix": time.time(),
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "records": self.records,
        }
        os.makedirs(self.out_dir, exist_ok=True)
        path = os.path.join(self.out_dir, f"BENCH_{self.name}.json")
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        return path


def bench(name: str) -> Bench:
    """A :class:`Bench` for section ``name`` (out dir from ``BENCH_DIR``)."""
    return Bench(name)


# -- engine introspection helpers ------------------------------------------

def engine_hlo(engine, chunk: int) -> Dict:
    """Deterministic HLO-cost columns for one compiled superstep: lower
    (not execute) a ``chunk``-round program and run the trip-count-aware
    cost model.  These are the hard-gated regression metrics."""
    from repro.launch.hlo_cost import analyse_hlo
    cost = analyse_hlo(engine.compiled_hlo(chunk))
    return {"flops": cost["flops"], "bytes": cost["bytes"],
            "collective_bytes": cost["collective_bytes"],
            "op_count_total": cost["op_count_total"],
            "collective_counts": cost["collective_counts"],
            "unknown_trip_whiles": cost["unknown_trip_whiles"],
            "chunk": chunk}


def sweep_experiment_records(b: "Bench", prefix: str, spec, logs,
                             *, extra_fidelity=None) -> list:
    """Fan one sweep dispatch's stacked outputs into one BENCH record
    per experiment plus aggregate mean/std rows.

    ``spec`` is the :class:`repro.dlrt.SweepSpec`, ``logs`` the
    per-experiment :class:`~repro.dlrt.MetricsLog` list a
    ``SweepSuperstep.run`` returned.  Each experiment lands as
    ``<prefix>/e<i>`` with its spec coordinates and final-record
    fidelity; the cross-experiment aggregate lands as
    ``<prefix>/agg_mean`` / ``<prefix>/agg_std`` (the fig3-style
    variance band).  ``extra_fidelity(e)`` may contribute extra
    per-experiment fidelity columns.  Returns the per-experiment final
    accuracies.
    """
    import numpy as np
    accs = []
    for e, log in enumerate(logs):
        rec = log.records[-1]
        fid = {"accuracy": rec.mean_accuracy, "loss": rec.mean_loss,
               "internode_variance": rec.internode_variance,
               "comm_bytes": rec.comm_bytes, **spec.describe(e)}
        if extra_fidelity is not None:
            fid.update(extra_fidelity(e))
        b.record(f"{prefix}/e{e}", f"{rec.mean_accuracy:.4f}",
                 fidelity=fid, print_csv=False)
        accs.append(rec.mean_accuracy)
    arr = np.asarray(accs, np.float64)
    b.record(f"{prefix}/agg_mean", f"{arr.mean():.4f}",
             fidelity={"accuracy_mean": float(arr.mean()),
                       "experiments": len(logs)})
    b.record(f"{prefix}/agg_std", f"{arr.std():.4f}",
             fidelity={"accuracy_std": float(arr.std()),
                       "accuracy_min": float(arr.min()),
                       "accuracy_max": float(arr.max())})
    return accs


def cpu_child_env(devices: int) -> Dict[str, str]:
    """Environment for a compile-only or simulated-device child process:
    the CPU backend with ``devices`` host devices.  The parent may hold
    the accelerator (a chip belongs to one process), so such a child
    never asks for it; what it measures is CPU mechanics and HLO
    counts."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count"
                          f"={devices}")
    env.setdefault("PYTHONPATH", "src")
    return env


def shape_dict(cfg, params) -> Dict:
    """The run's ``repro.tune`` shape key as a JSON-able dict."""
    import dataclasses

    from repro.tune import shape_of
    return dataclasses.asdict(shape_of(cfg, params))


def knobs_dict(cfg, resolved=None) -> Dict:
    """The knob assignment a run actually used: the runner's resolved
    knobs when available (``"auto"`` runs), else the raw config."""
    if resolved is not None:
        return {"chunk": resolved.chunk, "collective": resolved.collective,
                "block_d": resolved.block_d,
                "use_pallas": cfg.use_pallas, "source": resolved.source}
    return {"chunk": cfg.chunk, "collective": cfg.collective,
            "block_d": cfg.block_d, "use_pallas": cfg.use_pallas,
            "source": "explicit"}
