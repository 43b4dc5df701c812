"""chip_smoke.py's path rehearsed on CPU at smoke size: the engine built by
serve.py's own `build_engine` (paged KV, chunked prefill, the Pallas kernel
in interpret mode, a weight arena that forces tenant switches) passes every
platform-neutral check, and off a TPU the script refuses to run."""
import importlib.util
from pathlib import Path

import pytest

SMOKE_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_path_passes_at_smoke_size(chip_smoke):
    argv = chip_smoke.serve_argv(smoke=True, prompt_hi=20, gen=5, page=4,
                                 chunk=8, turn_steps=3)
    res = chip_smoke.run(argv, n_requests=4, prompt_lo=5,
                         log=lambda *a: None)
    assert chip_smoke.failures(res, need_kernel=False) == []
    assert res["switches"] >= 1
    assert sum(len(r.generated) for r in res["reqs"]) == 4 * 5
    # interpreted on CPU: the kernel lowers to plain XLA ops
    assert not res["decode_has_kernel"]


def test_smoke_refuses_to_run_off_tpu(chip_smoke, capsys):
    assert chip_smoke.main() == 1
    out, err = capsys.readouterr()
    assert out == ""                   # no result line, nothing printed
    assert "needs a TPU" in err
