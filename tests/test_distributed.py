"""Multi-device behaviour (8 virtual host devices via subprocess)."""
import os
import subprocess
import sys

import pytest

HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "multidevice_checks.py")


def run_check(name: str, timeout: int = 420, retries: int = 0, args=()):
    """Run one multidevice check in a subprocess.

    ``retries``: timing-based checks (calibrate-then-measure on a
    CPU-quota-throttled container) can skew when the box stalls mid-check;
    a retry must still pass the FULL check — assertions are never relaxed.
    ``args``: extra argv for parametrized checks (e.g. halo_edge cases).
    """
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    for attempt in range(retries + 1):
        out = subprocess.run([sys.executable, HELPER, name, *args], env=env,
                             capture_output=True, text=True, timeout=timeout)
        if "CHECK-PASSED" in out.stdout:
            return
        if attempt < retries:
            print(f"{name}: attempt {attempt + 1} failed, retrying "
                  f"(timing-sensitive check)")
    assert "CHECK-PASSED" in out.stdout, \
        f"{name} failed:\nstdout:{out.stdout[-2000:]}\nstderr:{out.stderr[-3000:]}"


@pytest.mark.slow
def test_pipeline_parallel():
    run_check("pipeline")


@pytest.mark.slow
def test_pipeline_train_step_gradient_parity():
    run_check("pipeline_step_parity")


@pytest.mark.slow
@pytest.mark.parametrize("schedule", ["gpipe", "one_f_one_b", "interleaved"])
def test_schedule_matches_serial_step(schedule):
    """Each pipeline schedule is gradient-exact vs the serial jit step on
    uniform and non-uniform LM cuts and on a heterogeneous CNN trunk."""
    run_check("schedule_parity", args=(schedule,))


@pytest.mark.slow
def test_schedule_bubble_and_oracle_winner():
    """Measured bubble fraction shrinks under 1F1B/interleaved vs GPipe at
    equal S, and the oracle's schedule axis picks the measured winner
    (ISSUE-7 acceptance). Timing-sensitive: retries re-run the FULL check."""
    run_check("schedule_validation", timeout=560, retries=2)


@pytest.mark.slow
def test_pipeline_plan_deploys_and_trains():
    run_check("pipeline_deploy")


@pytest.mark.slow
def test_pipeline_validation_measures():
    run_check("pipeline_validation", retries=1)


@pytest.mark.slow
def test_tuner_pick_beats_runner_up_measured():
    run_check("tuner_loop", retries=1)


@pytest.mark.slow
def test_halo_spatial_conv():
    run_check("halo")


@pytest.mark.slow
def test_halo_overlap_bit_exact():
    """Overlapped interior/boundary-split halo conv == serial pipeline ==
    unsharded SAME conv, bit-exact, incl. the deployed HaloConv + Pallas."""
    run_check("halo_overlap")


@pytest.mark.slow
@pytest.mark.parametrize("case", ["thin", "even", "p1", "stride", "padding"])
def test_halo_edge_cases(case):
    """H_local < halo raises; even kernel widths (asymmetric halos, incl.
    the Pallas path's empty lo=0 boundary) and p=1 stay bit-exact; strides
    are rejected with a clear error; non-SAME padding falls back to the
    plain conv instead of silently computing SAME."""
    run_check("halo_edge", args=(case,))


@pytest.mark.slow
def test_spatial_overlap_validation():
    """The measured ds (spatial-hybrid) step lands closer to the overlap
    oracle than to the serial-comm model (ISSUE-4 acceptance). Doubly
    timing-sensitive (calibrate-then-measure × model comparison), so it
    gets the widest retry budget; every retry re-runs the FULL check."""
    run_check("spatial_overlap_validation", timeout=560, retries=2)


@pytest.mark.slow
def test_dp_tp_numerics_match_single_device():
    run_check("dp_numerics")


@pytest.mark.slow
def test_summa_2d_gradient_parity():
    """SUMMA matmul + full train step under the summa rules on a
    (2,2,2) grid mesh are gradient-exact vs unsharded (ISSUE-9 tentpole)."""
    run_check("summa_parity")


@pytest.mark.slow
def test_tensor2d_oracle_winner_measured():
    """The tuned plan for a weight-heavy LM picks a 2D SUMMA lattice point
    and the oracle's winner is the measured winner (ISSUE-9 acceptance).
    Calibrate-then-measure: timing-sensitive, retries re-run the FULL
    check."""
    run_check("tensor2d_validation", timeout=560, retries=2)


@pytest.mark.slow
def test_serving_oracle_winner_measured():
    """Paged-cache serving under serve_tp and serve_seqkv on a 2-device
    mesh stays bit-exact vs the dense single-device reference, and the
    serving oracle's throughput winner is the measured winner (ISSUE-10
    acceptance). Timing-sensitive: retries re-run the FULL check."""
    run_check("serving_validation", timeout=560, retries=2)


@pytest.mark.slow
def test_oracle_validation_harness():
    run_check("oracle_validation")


@pytest.mark.slow
def test_compressed_gradient_allreduce():
    run_check("compressed_allreduce")


@pytest.mark.slow
def test_chip_smoke_sharded_phase():
    """The four-chip phase of chip_smoke.py on virtual devices: the trainer
    places state per the rules, and ds/df match one device."""
    run_check("chip_smoke_sharded")
