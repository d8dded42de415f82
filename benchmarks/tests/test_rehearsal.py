"""CPU rehearsal of ``run.py`` on the fixture cell: the whole of a run but the
look for a chip — and the same with the timed path broken underneath, where
``correct`` has to come out false. Also the CONTROL of the outputs check at a
size a test can hold: the reference in the next lower precision, put in the
program's place, fails the cell's limit."""
import json

import numpy as np
import pytest

from conftest import FIXTURES

from benchmarks import run as R
from benchmarks import check_served


@pytest.fixture(scope="module")
def untraced():
    return R.run_cell("tiny-serve", 2**31 + 11, 2.0, False, root=FIXTURES,
                      require_chip=False)


def test_result_line_has_the_contract_keys(untraced):
    res = untraced
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 10
    assert set(res["metrics"]) == {"serve_tok_s", "ttft_p95_ms", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    json.loads(json.dumps(res))
    for c in res["compared"].values():
        assert {"value", "limit"} <= set(c)
    assert res["compared"]["logit_gap_max"]["value"] <= 1e-3


def test_traced_run_reports_the_cells_layer_metrics_found_by_name():
    res = R.run_cell("tiny-serve", 7, 2.0, True, root=FIXTURES,
                     require_chip=False)
    # tick_ms.decode comes from benchmarks/layer_metrics, the fixture's own
    # metric from the fixture's directory: neither is registered in code
    assert set(res["metrics"]) == {"tick_ms.decode", "steps_in_window.fixture"}
    assert res["correct"] is True and "breakdown" in res


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from paddle_tpu.inference.serving import GenerationServer

    real = GenerationServer._harvest_window

    def altered(self, nxt_host, active, active_mask):
        nxt_host = np.array(nxt_host)
        nxt_host[:, ::2] = (nxt_host[:, ::2] + 1) % self.cfg.vocab_size
        return real(self, nxt_host, active, active_mask)

    monkeypatch.setattr(GenerationServer, "_harvest_window", altered)
    res = R.run_cell("tiny-serve", 2**31 + 12, 2.0, False, root=FIXTURES,
                     require_chip=False)
    assert res["correct"] is False
    c = res["compared"]["logit_gap_max"]
    assert c["value"] > c["limit"]


def test_a_request_cut_short_is_not_correct(monkeypatch):
    from paddle_tpu.inference.serving import GenerationServer

    real = GenerationServer._emit_result

    def short(self, req):
        real(self, req)
        self._results[req.rid] = self._results[req.rid][:-1]

    monkeypatch.setattr(GenerationServer, "_emit_result", short)
    res = R.run_cell("tiny-serve", 2**31 + 13, 2.0, False, root=FIXTURES,
                     require_chip=False)
    assert res["correct"] is False
    assert res["compared"]["wrong_shape"]["value"] > 0


def test_no_chip_is_an_error_not_a_fallback():
    with pytest.raises(SystemExit):
        R.device_stamp(1, {"TPU v5 lite": {}}, require_chip=True)


def test_control_lower_precision_fails_the_limit():
    """float32 fixture -> the control computes in bfloat16. It need not
    decode: the token the lower precision puts first, read in the float32
    reference, lies further below the best than the limit allows."""
    import importlib

    cfg = R.load_json(FIXTURES, "bench", "configs", "tiny-decoder.json")
    traffic = R.load_json(FIXTURES, "bench", "traffic", "tiny-mix.json")
    limits = R.load_json(FIXTURES, "bench", "limits", "tiny-serve.json")
    driver = importlib.import_module("benchmarks.drivers.serve_paged")
    # every finished request is compared: a flip of the first token needs a
    # near-tie, and some hundreds of tokens have one
    limits = dict(limits, sample_requests=64)
    ctx = R.Context(workload="tiny-serve", seed=5, seconds=3.0, trace=False,
                    config=cfg, traffic=traffic, chips=1,
                    t_process_start=R.T_PROCESS_START, scratch_dir="/tmp")
    run = driver.run(ctx)
    ok, compared = check_served.check(run, limits, 5)
    assert ok, compared
    worst = check_served.control_gap(run, limits, 5, mode="bf16")
    assert worst > 3 * max(compared["logit_gap_max"]["value"], 1e-6)
    assert worst > limits["logit_gap_max"]
