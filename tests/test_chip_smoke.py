"""``chip_smoke.py`` on the CPU: it reports no result off a TPU, and its
one-chip phases run end to end at a tiny grid, so the script keeps working
between runs on the chip."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_fails_without_tpu(monkeypatch, capsys, tmp_path):
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "use_compile_cache",
                        lambda: str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() == 1
    out, err = capsys.readouterr()
    assert "platform is 'cpu', not 'tpu'" in err
    assert '"ok"' not in out


def test_one_chip_phases_at_tiny_grid(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "REQUIRE_TPU", False)
    chip_smoke.run_one_chip(side=8)
    out = capsys.readouterr().out
    for phase in ("spmv[fp16/D15]", "spmv[e8m/D8]", "jacobi_pcg_stored",
                  "adaptive_pcg outer iters"):
        assert phase in out
