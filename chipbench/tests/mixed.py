"""A CPU-sized cell whose mix holds online aggregates (AVG) beside LIMIT
requests, written under a root of its own as a later PR would add one: the
``nt71_clustered`` configuration at the ``TINY`` size, 80% the
``nt71_browse`` templates, 20% ``chip_smoke``'s four aggregate templates."""
import json

from chipbench import harness, traffic
from chipbench.tests.tiny import TINY

# chip_smoke.make_requests' aggregates: (values, op)
AGG_TEMPLATES = [((1,), "and"), ((1, 1), "or"), ((1, 1), "and"), ((1, 0), "and")]

# a table whose measure 0 drifts with record position, so the densest blocks
# (the any-k chosen arm) are a biased sample of it
DRIFT_TABLE = '''
def make(cfg, seed):
    from repro.data.synthetic import make_clustered_table

    return make_clustered_table(
        num_records=int(cfg["num_records"]), num_dims=int(cfg["num_dims"]),
        num_measures=int(cfg["num_measures"]), density=float(cfg["density"]), seed=seed,
        correlated_measure=True, mean_cluster=int(cfg["mean_run"]))
'''


def aggregate_class(share: float = 0.2, measure=(0, 1), error_slo=(0.3, 0.4, 0.5)) -> dict:
    return {"share": share, "kind": "aggregate", "k": [2000], "measure": list(measure),
            "error_slo": list(error_slo),
            "templates": [{"op": op, "terms": [{"attr": "rotate", "value": v} for v in vals]}
                          for vals, op in AGG_TEMPLATES]}


def mixed_mix(rate: float = 5.0, outside_4se: int = 2, **agg) -> dict:
    browse = traffic.load_mix("nt71_browse")
    mix = {"about": "80% nt71_browse LIMIT, 20% AVG at error_slo 0.3-0.5",
           "arrivals": {"process": "poisson", "rate_per_s": rate}, "warmup_s": 0.5,
           "classes": [dict(browse["classes"][0], share=0.8), aggregate_class(**agg)]}
    if outside_4se is not None:
        mix["limits"] = {"agg_outside_4se": outside_4se}
    return mix


def mixed_cell(tmp_path, mix: dict | None = None, table: str | None = None):
    """Write the configuration, its table generator (``table``: the source
    of one, else the benchmark's own), the mix, the arrival process and a
    ``BENCHMARK.json`` with the one cell ``tiny-mixed``, and load it."""
    root = tmp_path / "bench"
    for d in ("configs", "traffic", "tables", "arrivals"):
        (root / d).mkdir(parents=True, exist_ok=True)
    cfg = harness.load_config("nt71_clustered")
    cfg.update(TINY["nt71-browse"], name="tiny_cfg")
    if table is not None:
        cfg["generator"] = "drift"
        (root / "tables" / "drift.py").write_text(table)
    else:
        (root / "tables" / "clustered.py").write_text(
            (harness.ROOT / "tables" / "clustered.py").read_text())
    (root / "configs" / "tiny_cfg.json").write_text(json.dumps(cfg))
    (root / "traffic" / "tiny_mixed.json").write_text(json.dumps(mix or mixed_mix()))
    (root / "arrivals" / "poisson.py").write_text(
        (harness.ROOT / "arrivals" / "poisson.py").read_text())
    spec = {"workloads": [{"name": "tiny-mixed", "config": "tiny_cfg",
                           "traffic": "tiny_mixed", "chips": 1, "why": "x"}],
            "end_to_end": [{"name": "p50_ms", "unit": "ms"},
                           {"name": "req_per_s", "unit": "req/s"}],
            "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.load_cell("tiny-mixed", repo=tmp_path, root=root)
