"""The sweep's centroids are judged in units of the reference's own last
Lloyd pass. At an instance whose reference is still in mid-move after that
pass, an honest float32 run passes and the bfloat16 control still fails; on
a settled instance the centroids are held to the limit itself, and on both a
shift of ten units fails."""

import copy
import json

import gen
import jax.numpy as jnp
import numpy as np
import pytest
import run
from conftest import BENCH

#: at 32768 rows, batch 0 of this seed leaves the reference's sweep (k = 6)
#: moving by 17% of its input's magnitude in its tenth and last pass;
#: batch 1 has settled
SEED, ROWS = 16, 32768
DRV = run.load_module(BENCH / "drivers" / "pipeline.py")
REF = run.load_module(BENCH / "reference" / "ds16-paper.py")
CONF = json.loads((BENCH / "configs" / "ds16-paper.json").read_text())
LIM = CONF["checks"]


@pytest.fixture(scope="module")
def batches():
    raws = gen.neubot_batches(CONF["data"], ROWS, CONF["batch"]["cols"], 2,
                              SEED)
    return [(raw, DRV.reference_outputs(REF, raw, CONF["operators"]))
            for raw in raws]


def _float32(raw):
    """The reference's operators in float32 with every matrix product at
    full precision: an honest float32 run."""
    def mm(a, b):
        return jnp.matmul(a, b, precision="highest")

    return DRV._np(REF.pipeline(raw, CONF["operators"], jnp, jnp.float32, mm))


def _fails(got, ref) -> bool:
    err, bad = DRV.compare(got, ref, LIM["near_tie"], LIM["sweep_settled"])
    return any(v > lim for v, lim in DRV.summarise([(err, bad)], LIM).values())


def _shifted(got, ref, by: float):
    """``got`` with the sweep's centroids moved by ``by`` of the input's
    magnitude."""
    out = copy.deepcopy(got)
    c, a, k = out["sweep_clustering"]["fit"]
    amax = float(np.abs(ref["pca"]["x"]).max())
    out["sweep_clustering"]["fit"] = (np.asarray(c) + by * amax, a, k)
    return out


def test_batches_settle_or_not(batches):
    (_r0, mid), (_r1, settled) = batches
    assert mid["sweep_step"] > LIM["sweep_settled"]
    assert settled["sweep_step"] <= LIM["sweep_settled"]


def test_float32_passes_and_bf16_fails_in_mid_move(batches):
    raw, ref = batches[0]
    assert not _fails(_float32(raw), ref)
    assert _fails(DRV.control_outputs(REF, raw, CONF["operators"], "bf16"), ref)


@pytest.mark.parametrize("which", [0, 1])
def test_centroids_judged_in_units_of_the_step(batches, which):
    raw, ref = batches[which]
    unit = LIM["rel_err.products"] * max(
        1.0, ref["sweep_step"] / LIM["sweep_settled"])
    assert (unit > LIM["rel_err.products"]) is (which == 0)
    got = _float32(raw)
    assert _fails(_shifted(got, ref, 10 * unit), ref)
    assert not _fails(_shifted(got, ref, 0.5 * unit), ref)


def test_program_is_correct_at_the_mid_move_instance(checkout):
    cell = run.load_cell("ds16-paper.closed", checkout, checkout / "bench")
    cell.config["batch"]["rows"] = ROWS
    res = run.execute("ds16-paper.closed", SEED, 1.0, False, root=checkout,
                      bench=checkout / "bench", platform="cpu", cell=cell)
    assert res["correct"] is True
