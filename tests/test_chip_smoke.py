"""CPU rehearsal of ``chip_smoke.py``: its phases at the smoke config of
smollm-135m, with the Pallas kernels in interpret mode. The device check is
steered here, not through an option of the script: the phases are called
directly, and the check itself must refuse this CPU-only process."""
import importlib.util
import os
import sys

import jax
import pytest

from repro.configs import get_smoke_config

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                     "chip_smoke.py")
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = sys.modules.setdefault(
    "chip_smoke", importlib.util.module_from_spec(_spec))
_spec.loader.exec_module(chip_smoke)

SMALL = chip_smoke.Sizes(calib=8, seq=16, iters=2, batch=4, slots=4,
                         requests=8, max_new=4, prompt_min=4, prompt_max=24,
                         max_len=32, min_bucket=8)


def test_device_check_refuses_cpu(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("this process holds a TPU")
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.check_device(1)
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU: jax found" in out.err


def test_phases_on_smoke_config():
    cfg = get_smoke_config(chip_smoke.ARCH)
    q = chip_smoke.quantize(cfg, 0, SMALL)
    assert len(q.reports) == cfg.n_layers
    reqs = chip_smoke.serve(q, 0, SMALL, backend="pallas", interpret=True)
    assert len(reqs) == SMALL.requests
    assert chip_smoke.kernel_parity(q, backend="pallas", interpret=True
                                    ) <= chip_smoke.KERNEL_REL_TOL
    res = chip_smoke.compare_reference(q, reqs, backend="pallas",
                                       interpret=True)
    assert sorted(res) == ["A8", "weight-only"]
    assert all(r["max_abs_err"] <= r["bound"] for r in res.values())


def test_data_parallel_phase_on_four_devices(monkeypatch):
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4)")
    # deploy matmuls on (interpreted) Pallas kernels, as on the chip, so the
    # PTQ student takes its per-shard path
    import repro.kernels.ops as kops
    monkeypatch.setattr(kops, "resolve_backend",
                        lambda backend="auto", interpret=None: ("pallas",
                                                                True))
    res = chip_smoke.data_parallel_parity(
        get_smoke_config(chip_smoke.ARCH), 0, 4, SMALL)
    assert res["err_dev_max"] <= chip_smoke.ERR_RTOL
