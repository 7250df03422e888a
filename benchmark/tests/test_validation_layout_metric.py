"""What PR 27 adds to the benchmark: ``validation_layout_s``, the seconds of
the program's ``validation_layout`` + ``validation_upload`` spans under the
process's FIRST ``coordinate_descent`` (the warm-up fit, where a tiled
coordinate lays the validation rows out like its own design). A rehearsal of
each cell prints it; a program without the spans (this PR's parent, a COO
coordinate) prints nothing."""

import json

import pytest

from benchmark import run
from benchmark.readers import program_span_seconds

with open(run.os.path.join(run.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
METRIC = next(m for m in BENCH["per_layer"] if m["name"] == "validation_layout_s")


@pytest.mark.parametrize("cell", METRIC["workloads"])
def test_rehearsal_prints_the_validation_layout(capsys, cell):
    from photon_ml_tpu import telemetry

    # a run is a process of its own: the metric reads the process's FIRST
    # coordinate_descent, so forget what earlier tests ran
    telemetry.reset()
    rc = run.main(["--workload", cell, "--seed", "3000000019", "--seconds",
                   "0.1", "--trace", "1", "--rehearsal-rows", "3000"])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["metrics"]["validation_layout_s"]["value"] > 0
    assert line["metrics"]["validation_layout_s"]["unit"] == "s"
    # laid out once, in the warm-up fit; every later fit is a cache hit,
    # and the training design's own counters are the training design's
    c = telemetry.snapshot()["counters"]
    assert c["validate.design_builds"] == 1
    assert c["validate.design_hits"] == line["attempted"]
    assert "validate.coo_scores" not in c
    assert c["validate.layout.nnz"] < c["layout.nnz"]


def test_a_program_without_the_spans_prints_nothing():
    from photon_ml_tpu import telemetry

    telemetry.reset()
    with telemetry.span("coordinate_descent"):
        with telemetry.span("validate"):
            pass
    params = run.load_json("metrics", "validation_layout_s.json")["params"]
    assert program_span_seconds.read({}, **params) is None
    telemetry.reset()
    with telemetry.span("coordinate_descent"):
        with telemetry.span("validate"):
            with telemetry.span("validation_layout"):
                pass
            with telemetry.span("validation_upload"):
                pass
    with telemetry.span("coordinate_descent"):  # a later fit: a cache hit
        pass
    assert program_span_seconds.read({}, **params) > 0
