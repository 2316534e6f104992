"""The plain ``--smoke`` run of each workload through ``driver.run``: the
verdict's keys in order, the metric, ``fault.verified`` and the phases'
messages in order, pinned from the parent (tests/test_driver_smoke.py holds
the pins and the switches of one workload; ``spmv`` also runs with
``--synth-collectives``, the one switch that is its own)."""

import pytest
from test_driver_smoke import check_smoke_verdict


@pytest.mark.needs_pinned_host
@pytest.mark.parametrize("case", ["halo", "spmv", "attn", "mla_decode",
                                  "dsa_decode", "kda_decode", "moe",
                                  "synth"])
def test_smoke_workload_verdict_is_pinned(case, capfd):
    check_smoke_verdict(case, capfd)
