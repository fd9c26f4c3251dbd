"""``correct`` on small cells: sound runs pass; the control and every fault
a one-chip cell can have fail."""
import numpy as np
import pytest

from bench.control import ReferenceServer
from bench.served import Served
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["small.ingest", "small.query"])
def test_sound_runs_are_correct(root, cell):
    out = tiny.run(root, cell, seed=2**31 + 7)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(v["value"] == 0 for v in out["checks"].values())
    assert list(out)[-1] == "checks"


def test_control_in_int16_is_not_correct(root):
    out = tiny.run(root, "small.ingest", make_server=ReferenceServer)
    assert not out["correct"]
    assert out["checks"]["table_cells_wrong"]["value"] > 0


def test_the_reference_in_int32_is_correct(root):
    """The control differs from a sound server by its width alone."""
    wide = lambda *a: ReferenceServer(*a, dtype=np.int32)  # noqa: E731
    assert tiny.run(root, "small.ingest", make_server=wide)["correct"]


class StateUnchanged(Served):
    def ingest(self, items, freqs):
        pass


class HalfBatch(Served):
    def ingest(self, items, freqs):
        half = items.shape[0] // 2
        super().ingest(items[:half], freqs[:half])


class AnswerAltered(Served):
    def result(self, handle):
        got = super().result(handle)
        if got is None or not len(got[1]):
            return got
        est = np.array(got[1])
        est[-1] += 1
        return got[0], est


@pytest.mark.parametrize("fault", [StateUnchanged, HalfBatch, AnswerAltered])
def test_faults_are_not_correct(root, fault):
    out = tiny.run(root, "small.ingest", make_server=fault)
    assert not out["correct"], fault.__name__
