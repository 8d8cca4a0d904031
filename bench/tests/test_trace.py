"""The trace reduction on two small windows recorded on a TPU v5e chip (cut
from longer traces: a federated round under attack, and serving decode
steps with an admission)."""
from pathlib import Path

import pytest

from bench import kernels, trace

DATA = Path(__file__).parent / "data"


def _load(name):
    space = trace.xplane_pb2().XSpace()
    space.ParseFromString((DATA / f"{name}.xplane.pb").read_bytes())
    return trace.Trace.from_space(space)


@pytest.fixture(scope="module")
def fl():
    return _load("fl_attack_window")


@pytest.fixture(scope="module")
def serve():
    return _load("serve_short_window")


@pytest.mark.parametrize("name", ["fl", "serve"])
def test_busy_and_idle_partition_the_window(name, request):
    tr = request.getfixturevalue(name)
    idle = sum(e - s for s, e in tr.idle_gaps()) / 1e9
    assert 0 < tr.busy_s <= tr.window_s
    assert tr.busy_s + idle == pytest.approx(tr.window_s, abs=1e-9)
    assert sum(v for _, v in tr.idle_by_host(1000)) == pytest.approx(idle)


def test_fl_window(fl):
    assert fl.window_s == pytest.approx(0.05)
    assert fl.busy_s == pytest.approx(0.049869075)
    assert fl.has_scopes()
    assert fl.scope_time("client_update") == pytest.approx(0.00147707)
    assert fl.scope_time("aggregate") == pytest.approx(0.001290147)
    # the attacker's gathers, outside any named scope, lead
    assert [n for n, _ in fl.top_ops(3)] == ["fusion.411", "fusion.410",
                                             "fusion.409"]
    assert fl.device_time(kernels.is_paged_decode) == 0


def test_scope_matches_whole_components(fl):
    assert fl.scope_time("client") == 0
    assert fl.scope_time("client_update") > 0


def test_serve_window(serve):
    assert serve.window_s == pytest.approx(0.16)
    assert serve.program_runs("decode") == [pytest.approx(0.069583648)]
    assert serve.program_runs("admit") == [pytest.approx(0.036286963)]
    assert serve.device_time(kernels.is_paged_decode) == \
        pytest.approx(0.049840286)
    assert serve.top_ops(1)[0][0] == "checkpoint.7"


def test_program_names():
    assert trace.program_name("jit_decode(349892380730773120)") == "decode"
    assert trace.program_name("jit__sample(1)") == "_sample"
