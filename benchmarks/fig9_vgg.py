"""Fig 9: layer-wise VGG-16 utilization and clock cycles per array size,
from the analytical model (eq 10-12), plus the schedule cache's fold-reuse
metric over the full-size 13-layer walk.  Times nothing: the served
path is measured on the chip by ``chipbench/run.py``.
"""
from repro.core.engine import ScheduleCache
from repro.core.folds import PEArray, decompose
from repro.core.loopnest import vgg16_conv_layers
from repro.core.perfmodel import t_ops_cycles


def rows():
    out = []
    for name, cv in vgg16_conv_layers():
        row = {"layer": name}
        for pe in (16, 32, 64):
            plan = decompose(cv, PEArray(pe, pe))
            row[f"util_{pe}"] = round(plan.avg_utilization(), 2)
            row[f"cycles_{pe}_M"] = round(t_ops_cycles(plan) / 1e6, 3)
        out.append(row)
    return out


def fold_reuse_metric() -> dict:
    """Schedule-cache behaviour over the full-size 13-layer walk."""
    cache = ScheduleCache()
    for _, cv in vgg16_conv_layers():
        cache.schedule_for(cv)
    d = cache.stats.as_dict()
    d["distinct_schedules"] = cache.distinct
    return d


def main(csv=False):
    print("# Fig 9 — VGG-16 layer-wise utilization (a) and cycles (b)")
    hdr = ("layer", "util_16", "util_32", "util_64",
           "cycles_16_M", "cycles_32_M", "cycles_64_M")
    print(",".join(hdr))
    for r in rows():
        print(",".join(str(r[h]) for h in hdr))
    late = [r for r in rows() if not r["layer"].startswith("conv1_1")]
    u64_min = min(r["util_64"] for r in late)
    print(f"# 64x64 utilization >90% on all layers past conv1_1: "
          f"{u64_min > 90} (min {u64_min}%)")
    fr = fold_reuse_metric()
    print(f"# fold reuse (full-size): {fr['distinct_schedules']} schedules "
          f"for 13 layers, {fr['hits']} cache hits "
          f"(hit_rate={fr['hit_rate']})")
    return u64_min


if __name__ == "__main__":
    main()
