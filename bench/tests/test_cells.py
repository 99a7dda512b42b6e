"""Each cell of BENCHMARK.json resolves by name to its configuration,
its traffic mix and its metrics, each with a reader."""

import json

import pytest

from harness import BENCH, ROOT, load_cell, reader

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
BATCH_METRICS = {
    "device.busy_us_per_query.batch", "device.idle_share.batch",
    "beam.evals_per_query.batch", "bq_distance_roofline.batch",
    "beam.iterations_per_batch.batch", "beam.lockstep_share.batch",
    "beam.us_per_iteration.batch",
}


def test_dbpedia1536_cell_resolves():
    cell = load_cell("dbpedia1536.batch-closed")
    assert cell.chips == 1
    cfg = cell.config
    assert cfg["name"] == "dbpedia-1536-32k"
    assert cfg["corpus"] == {"generator": "contrastive_surrogate", "d": 1536,
                             "n_topics": 64, "subclusters": 16,
                             "intrinsic": 1536 // 8}
    assert (cfg["n"], cfg["pool"], cfg["corpus_seed"]) == (32000, 2048, 1536)
    # the source's operating point
    assert (cfg["k"], cfg["ef"], cfg["nav"]) == (10, 64, "bq2")
    assert cfg["build"]["m"] == 16 and cfg["build"]["ef_construction"] == 96
    assert cfg["recall_at_10_min"] == 0.88
    assert cfg["published"]["n"] == 500000 and "n" in cfg["reduced"]
    assert cell.mix == json.loads(
        (BENCH / "traffic" / "batch-closed.json").read_text())
    assert {m["name"] for m in cell.end_to_end} == {
        "qps", "recall_at_10", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == BATCH_METRICS | {"build_s"}


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_builds_and_reads_its_metrics(name):
    from repro.core.vamana import BuildParams

    cell = load_cell(name)
    BuildParams(**cell.config["build"])
    for m in cell.end_to_end + cell.per_layer:
        assert callable(reader(m["name"]))
