"""With no accelerator, the chip entry point fails and says why.

``chip_smoke.py`` proves something ON the chip; in this sandbox JAX is held
to the CPU, and it may not print a result under a device's name or exit 0
(the driver runs the smoke here first and requires exactly that).  What the
smoke does on a chip is not tested here: it is run there (PERF.md)."""

import json


def test_chip_smoke_fails_without_an_accelerator(capsys):
    import chip_smoke

    assert chip_smoke.main([]) != 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert lines and lines[-1]["ok"] is False
    assert "no accelerator" in lines[-1]["reason"]
    assert not any(line.get("ok") is True for line in lines)
    assert not any("device" in line for line in lines)
