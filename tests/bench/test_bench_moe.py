"""The MoE cell's check refuses a program that routes otherwise than
DeepSeek-V2-Lite: one that renormalises the top 6 gates
(``norm_topk_prob`` is false in its config), and the training path's
capacity router, which drops tokens over capacity (and renormalises)."""

import dataclasses

import pytest

from bench_helpers import make_tiny_root, run_cell

CELL = "dsv2lite-moe-prefill-n2048"


def _renormalised(serve):
    def other(self, p, cfg, x, **kw):
        return serve(self, p, dataclasses.replace(cfg, norm_topk_prob=True),
                     x, **kw)
    return other


def _capacity(serve):
    def other(self, p, cfg, x, **kw):
        return self.apply(p, cfg, x[None])[0]
    return other


@pytest.mark.parametrize("router", [_renormalised, _capacity])
def test_check_refuses_another_router(router, tmp_path, monkeypatch):
    from repro.models.layers import SparseMoE

    monkeypatch.setattr(SparseMoE, "serve", router(SparseMoE.serve))
    res = run_cell(make_tiny_root(tmp_path, CELL), CELL)
    assert not res["correct"], res["check"]
    assert res["check"]["row_rel_err"]["value"] > 1e-3
