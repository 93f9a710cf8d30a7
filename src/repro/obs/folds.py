"""Per-schedule streaming counters (DESIGN.md §11): the paper's Fig 9
layer-wise utilization profile and Table 3 fold-reuse numbers as *running*
counters over live traffic, instead of offline bench scripts.

For every distinct ``ScheduleKey`` a served network executes, we join

* the **analytical model side** — ``perfmodel.layer_perf`` on the
  schedule's planned nest (eq 10 average PE utilization, eq 11 T_Ops,
  eq 12 GFLOP/s) and ``engine.dataflow_traffic_bytes`` for the selected
  dataflow (modeled HBM bytes moved), normalized per inference, with

* the **measured side** — wall-clock kernel time per dispatched batch,
  apportioned across the network's layers by each layer's share of the
  modeled T_Ops (a jitted forward is one opaque device call; the
  apportionment is the model's own prediction of where the time goes and
  is tagged as such wherever it is surfaced).

The quotient — achieved GFLOP/s over the model's eq-12 GFLOP/s — is the
live achieved-vs-roofline column.  On this container's interpret-mode
CPU backend it is honest about being far below 100%; on a real TPU it
becomes the paper's Fig 9 comparison.

Pure numpy/Python; no jax imports, so the report CLI can render a
model-side table without touching a device.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from repro.core.engine import ConvSchedule, dataflow_traffic_bytes
from repro.core.folds import PEArray
from repro.core.perfmodel import MavecConfig, layer_perf

__all__ = ["model_layer_stats", "FoldStreamCounters"]


def model_layer_stats(sched: ConvSchedule, pe: PEArray,
                      cfg: Optional[MavecConfig] = None) -> dict:
    """The analytical-model row for one compiled schedule, normalized
    per inference (the planned nest's batch divided out)."""
    cfg = cfg or MavecConfig()
    nest = sched.nest
    lp = layer_perf(nest, pe, cfg)
    # bytes are modeled at the *streamed* dtype: int8 schedules move
    # 1-byte weight/activation folds (psum staging stays 4-byte int32)
    traffic = dataflow_traffic_bytes(nest, sched.plan, cfg.bytes_per_elem,
                                     precision=sched.key.precision)
    bytes_batch = traffic.get(sched.dataflow,
                              traffic.get("weight_stationary", 0.0))
    n = max(nest.n, 1)
    return {
        "key": str(sched.key),
        "dataflow": sched.dataflow,
        "precision": sched.key.precision,
        "util_model_pct": round(lp.util_avg_pct, 2),
        "t_ops_cycles": lp.t_ops,
        "gflops_model": round(lp.gflops, 2),
        "flops_per_inf": nest.flops / n,
        "bytes_per_inf": bytes_batch / n,
    }


class _SchedCounters:
    """Running totals for one ScheduleKey."""

    __slots__ = ("model", "layers", "dispatches", "items", "time_s")

    def __init__(self, model: dict) -> None:
        self.model = model
        self.layers: List[str] = []
        self.dispatches = 0
        self.items = 0
        self.time_s = 0.0

    def row(self) -> dict:
        m = self.model
        flops = m["flops_per_inf"] * self.items * len(self.layers or [1])
        achieved = (flops / self.time_s / 1e9) if self.time_s > 0 else 0.0
        vs_model = (achieved / m["gflops_model"] * 100.0
                    if m["gflops_model"] else 0.0)
        return {
            "key": m["key"],
            "dataflow": m["dataflow"],
            "precision": m["precision"],
            "layers": list(self.layers),
            "util_model_pct": m["util_model_pct"],
            "t_ops_cycles": m["t_ops_cycles"],
            "gflops_model": m["gflops_model"],
            "dispatches": self.dispatches,
            "items": self.items,
            "measured_s": round(self.time_s, 6),
            "bytes_moved_model": m["bytes_per_inf"] * self.items
            * len(self.layers or [1]),
            "achieved_gflops": round(achieved, 4),
            "achieved_vs_model_pct": round(vs_model, 4),
        }


class FoldStreamCounters:
    """Live per-ScheduleKey utilization / bytes-moved / achieved-vs-model
    table.

    ``observe_compile`` registers a compiled network's layer → schedule
    mapping (idempotent per layer name); ``observe_dispatch`` folds one
    measured kernel interval into the per-schedule totals and returns the
    per-layer apportionment.
    """

    def __init__(self, pe: Optional[PEArray] = None,
                 cfg: Optional[MavecConfig] = None) -> None:
        self.pe = pe or PEArray(16, 16)
        self.cfg = cfg or MavecConfig()
        self._by_key: Dict[str, _SchedCounters] = {}
        self._layer_key: Dict[str, str] = {}    # layer name -> key str
        self._layer_tops: Dict[str, int] = {}   # layer name -> model t_ops

    # -- registration ------------------------------------------------------
    def observe_compile(
            self, layer_schedules: Sequence[Tuple[str, ConvSchedule]]
    ) -> None:
        for name, sched in layer_schedules:
            k = str(sched.key)
            sc = self._by_key.get(k)
            if sc is None:
                sc = _SchedCounters(model_layer_stats(sched, self.pe,
                                                      self.cfg))
                self._by_key[k] = sc
            if name not in self._layer_key:
                sc.layers.append(name)
            self._layer_key[name] = k
            self._layer_tops[name] = sc.model["t_ops_cycles"]

    # -- measurement -------------------------------------------------------
    def apportion(
            self, layer_schedules: Sequence[Tuple[str, ConvSchedule]],
            kernel_time_s: float
    ) -> List[Tuple[str, str, float]]:
        """Split one measured kernel interval across layers by modeled
        T_Ops share: ``[(layer, key_str, dur_s), ...]`` in layer order."""
        self.observe_compile(layer_schedules)
        names = [name for name, _ in layer_schedules]
        total = float(sum(self._layer_tops[n] for n in names)) or 1.0
        return [(n, self._layer_key[n],
                 kernel_time_s * self._layer_tops[n] / total)
                for n in names]

    def observe_dispatch(
            self, layer_schedules: Sequence[Tuple[str, ConvSchedule]],
            items: int, kernel_time_s: float
    ) -> List[Tuple[str, str, float]]:
        """Fold one dispatched batch (``items`` inferences, one measured
        device interval) into the running totals.  Returns the per-layer
        apportionment (same contract as ``apportion``)."""
        parts = self.apportion(layer_schedules, kernel_time_s)
        seen_keys = set()
        for _, k, dur in parts:
            sc = self._by_key[k]
            sc.time_s += dur
            if k not in seen_keys:
                seen_keys.add(k)
                sc.dispatches += 1
                sc.items += int(items)
        return parts

    # -- export ------------------------------------------------------------
    def rows(self) -> List[dict]:
        return [self._by_key[k].row() for k in sorted(self._by_key)]

    @property
    def util_model_pct(self) -> float:
        """Mean eq-10 utilization across distinct schedules — the
        headline the paper quotes (>90% for VGG-16 on 64x64)."""
        rows = self.rows()
        if not rows:
            return 0.0
        return sum(r["util_model_pct"] for r in rows) / len(rows)

    def as_dict(self) -> dict:
        return {
            "pe_array": f"{self.pe.rp}x{self.pe.cp}",
            "distinct_schedules": len(self._by_key),
            "conv_layers": len(self._layer_key),
            "util_model_pct": round(self.util_model_pct, 2),
            "schedules": {r["key"]: r for r in self.rows()},
        }

    def table(self) -> str:
        """Human-readable per-schedule table (the report CLI output)."""
        hdr = (f"{'schedule':<24} {'dataflow':<18} {'lyr':>3} "
               f"{'util%':>6} {'GF/s(mdl)':>10} {'disp':>5} {'items':>6} "
               f"{'meas(s)':>8} {'MB(mdl)':>9} {'GF/s':>8} {'vs-mdl%':>8}")
        lines = [hdr, "-" * len(hdr)]
        for r in self.rows():
            lines.append(
                f"{r['key']:<24} {r['dataflow']:<18} "
                f"{len(r['layers']):>3} {r['util_model_pct']:>6.2f} "
                f"{r['gflops_model']:>10.2f} {r['dispatches']:>5} "
                f"{r['items']:>6} {r['measured_s']:>8.3f} "
                f"{r['bytes_moved_model'] / 1e6:>9.2f} "
                f"{r['achieved_gflops']:>8.3f} "
                f"{r['achieved_vs_model_pct']:>8.3f}")
        lines.append(f"mean model utilization: "
                     f"{self.util_model_pct:.2f}% over "
                     f"{len(self._by_key)} schedules / "
                     f"{len(self._layer_key)} conv layers "
                     f"[PE {self.pe.rp}x{self.pe.cp}]")
        return "\n".join(lines)
