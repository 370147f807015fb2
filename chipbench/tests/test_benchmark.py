"""BENCHMARK.json and the files it names hang together."""

import dataclasses

import pytest

from chipbench import adapter, spec, weights

BENCH = spec.load_json(spec.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_with_its_metrics(name):
    cell = spec.cell(name)
    assert {m["name"] for m in cell.end_to_end} > {"setup_s"}
    assert cell.per_layer
    e2e = {m["name"] for m in cell.end_to_end}
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(spec.reader(m["name"]).read)
    assert cell.traffic["check"]["limit"] > 0


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_is_the_programs(conf):
    """The sizes run are the program's registered ones for that model, and
    the weights the benchmark makes have the program's layout."""
    from repro.configs import get_config
    config = spec.load_json(spec.ROOT / conf["file"])
    mdl = adapter.model(config)
    want = get_config(config["arch"])
    for f in dataclasses.fields(want):
        if f.name not in ("name", "source"):
            assert getattr(mdl.cfg, f.name) == getattr(want, f.name), f.name
    shapes = {p: s for p, (s, _) in weights.shapes(config).items()}
    import jax
    specs = jax.tree_util.tree_flatten_with_path(mdl.param_specs())[0]
    got = {"/".join(k.key for k in path): tuple(leaf.shape)
           for path, leaf in specs}
    assert got == shapes


def test_kernel_counts_exist_for_rooflines():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline"):
            k = spec.kernel(m["name"][:-len("_roofline")])
            assert k.SPAN in ("admit", "decode") and callable(k.work)


def test_a_suffixed_metric_is_measured_as_its_base():
    """``itl_p90_ms.short`` is ``itl_p90_ms``, held to its own bound."""
    from types import SimpleNamespace

    from chipbench import harness
    srv = SimpleNamespace(
        sched=SimpleNamespace(n_window=2, due=[0.0, 1.0]),
        times=[[0.1, 0.2, 0.4], [1.3, 1.4]])
    cell = dataclasses.replace(spec.cell(CELLS[0]), end_to_end=(
        {"name": "itl_p90_ms", "unit": "ms"},
        {"name": "itl_p90_ms.short", "unit": "ms"},
        {"name": "ttft_p50_ms", "unit": "ms"}))
    out = harness._end_to_end(cell, srv, 1.0)
    assert out["itl_p90_ms.short"] == out["itl_p90_ms"]
    assert out["itl_p90_ms"]["value"] == pytest.approx(200.0)
    assert out["ttft_p50_ms"]["value"] == pytest.approx(100.0)
