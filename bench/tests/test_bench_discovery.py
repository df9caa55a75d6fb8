"""A configuration, a traffic mix, a per-layer metric and a cell are added
as new files and new ``BENCHMARK.json`` entries; no existing file changes."""
import hashlib
import json
import shutil

from bench import harness


def _digest(root):
    return {p.relative_to(root).as_posix(): hashlib.sha256(
        p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*")) if p.is_file()
        and "__pycache__" not in p.parts}


def test_new_pieces_are_found_by_name(tmp_path):
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load_benchmark()
    before = _digest(tmp_path / "bench")

    cfg = json.loads((tmp_path / "bench/configs/rff_gp_n5000.json")
                     .read_text())
    cfg.update(name="rff_gp_n20000", n=20000)
    (tmp_path / "bench/configs/rff_gp_n20000.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/window_k4.json").write_text(json.dumps(
        {"loop": "closed", "rows_per_step": 4, "pool_batches": 2048}))
    (tmp_path / "bench/limits/gp.n20000.k4.json").write_text(json.dumps(
        {"limits": {"factor_rel_err": 1e-3}}))
    (tmp_path / "bench/layer_metrics/steps.gp.py").write_text(
        "def read(record):\n    return record['steps']\n")
    bench["configs"].append({
        "name": "rff_gp_n20000", "source": "https://arxiv.org/abs/1011.1173",
        "file": "bench/configs/rff_gp_n20000.json", "reduced": [],
        "why": "a larger factor"})
    bench["workloads"].append({
        "name": "gp.n20000.k4", "config": "rff_gp_n20000",
        "traffic": "window_k4", "chips": 1, "why": "a larger factor"})
    bench["end_to_end"][1]["workloads"].append("gp.n20000.k4")
    bench["per_layer"].append({
        "name": "steps.gp", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "6 device",
        "moves": "step_ms", "workloads": ["gp.n20000.k4"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before

    found = harness.load_benchmark(tmp_path)
    cell = harness.cell_named(found, "gp.n20000.k4")
    assert harness.config_for(found, cell, tmp_path)["n"] == 20000
    assert harness.traffic_for(cell, tmp_path)["rows_per_step"] == 4
    assert harness.limits_for(cell, tmp_path) == {"factor_rel_err": 1e-3}
    assert [m["name"] for m in harness.metrics_of(
        found, cell, "per_layer")] == ["steps.gp"]
    assert [m["name"] for m in harness.metrics_of(
        found, cell, "end_to_end")] == ["setup_s", "step_ms"]
    assert harness.metric_reader("steps.gp", tmp_path)({"steps": 7}) == 7
    driver = harness.driver_for(harness.config_for(found, cell, tmp_path),
                                tmp_path)
    assert callable(driver.run)


def test_every_named_piece_exists():
    bench = harness.load_benchmark()
    for cell in bench["workloads"]:
        cfg = harness.config_for(bench, cell)
        assert callable(harness.driver_for(cfg).run)
        assert harness.traffic_for(cell)
        assert harness.limits_for(cell)
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
