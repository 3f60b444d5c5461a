"""chip_smoke.py's checks at the reduced RM1 on CPU (interpret mode), and
its refusal to run without a TPU."""
import importlib.util
import os

import pytest

from repro.configs import rm1

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_checks_pass_at_reduced_size(chip_smoke):
    res = chip_smoke.run(rm1.REDUCED, seed=0, requests=8)
    assert res["completed"]
    assert res["ab_bitwise"]
    assert res["pooled_bitwise_seq_ref"]
    assert res["pooled_diff"] <= chip_smoke.POOLED_ATOL
    assert all(d <= chip_smoke.SCORE_ATOL for d in res["score_diff"].values())
    # interpret mode lowers the kernel body to plain HLO: no Mosaic call
    assert res["kernel_in_hlo"] == {"ddr_mn": False, "nmp_mn": False}


def test_smoke_refuses_cpu(chip_smoke, capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err
