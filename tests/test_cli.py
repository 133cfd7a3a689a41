"""Miniapp CLIs driven in-process on the CPU test platform — covers the
reference's driver surface (`examples/conflux_miniapp.cpp`,
`examples/cholesky_miniapp.cpp`) including the `_result_` protocol."""

import re

import pytest

from conflux_tpu.cli import cholesky_miniapp, conflux_miniapp


def run_cli(main, argv, capsys):
    rc = main(argv)
    assert rc == 0
    return capsys.readouterr().out


def test_conflux_miniapp_result_line(capsys):
    out = run_cli(
        conflux_miniapp.main,
        ["-N", "64", "-b", "16", "--p_grid", "2,2,1", "-r", "2", "--validate",
         "--dtype", "float64"],
        capsys,
    )
    lines = [l for l in out.splitlines() if l.startswith("_result_")]
    assert len(lines) == 2
    m = re.match(
        r"_result_ lu,conflux_tpu,64,32,4,2x2x1,time,weak,([\d.]+),16,float64",
        lines[0]
    )
    assert m, lines[0]
    res = [l for l in out.splitlines() if l.startswith("_residual_")]
    assert len(res) == 1
    assert float(res[0].split()[1]) < 1e-10


def test_conflux_miniapp_auto_grid(capsys):
    out = run_cli(conflux_miniapp.main, ["-N", "64", "-b", "8", "-r", "1"], capsys)
    assert "_result_" in out


def test_conflux_miniapp_grid_too_large():
    with pytest.raises(SystemExit):
        conflux_miniapp.main(["-N", "64", "-b", "8", "--p_grid", "4,4,4"])


def test_cholesky_miniapp(capsys):
    out = run_cli(
        cholesky_miniapp.main,
        ["--dim", "64", "--tile", "16", "--grid", "2,2,2", "--run", "2", "--validate"],
        capsys,
    )
    assert "PROBLEM PARAMETERS" in out
    lines = [l for l in out.splitlines() if l.startswith("_result_")]
    assert len(lines) == 2
    assert lines[0].startswith("_result_ cholesky,conflux_tpu,64,32,8,2x2x2,time,weak,")
    res = [l for l in out.splitlines() if l.startswith("_residual_")]
    assert float(res[0].split()[1]) < 1e-4


def test_profiler_report(capsys):
    from conflux_tpu import profiler

    profiler.clear()
    with profiler.region("step0_reduce"):
        pass
    with profiler.region("step0_reduce"):
        pass
    t = profiler.timings()
    assert t["step0_reduce"][0] == 2
    out = profiler.report()
    assert "step0_reduce" in out
    profiler.clear()
    assert profiler.timings() == {}


def test_cholesky_helper_roundtrip(tmp_path, capsys):
    """generate -> factor -> compare pipeline (the reference's
    cholesky_helper + compare_res.py workflow)."""
    from conflux_tpu.cli import cholesky_helper

    inp = str(tmp_path / "input_64.bin")
    ref = str(tmp_path / "result_64.bin")
    mine = str(tmp_path / "mine_64.bin")
    rc = cholesky_helper.main(
        ["generate", "--dim", "64", "--out", inp, "--result", ref,
         "--dtype", "float64"]
    )
    assert rc == 0
    rc = cholesky_helper.main(
        ["factor", inp, mine, "--tile", "16", "--grid", "2,2,1",
         "--dtype", "float64"]
    )
    assert rc == 0
    rc = cholesky_helper.main(["compare", mine, ref, "--lower", "--tol", "1e-8"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "_compare_" in out


def test_cholesky_helper_compare_fails_above_tol(tmp_path, capsys):
    import numpy as np

    from conflux_tpu.cli import cholesky_helper
    from conflux_tpu.io import save_matrix

    a, b = str(tmp_path / "a.bin"), str(tmp_path / "b.bin")
    save_matrix(a, np.eye(8))
    save_matrix(b, 2 * np.eye(8))
    assert cholesky_helper.main(["compare", a, b, "--tol", "1e-3"]) == 1


def test_cholesky_helper_reads_reference_raw_format(tmp_path):
    """The reference cholesky_helper writes raw headerless dim*dim doubles;
    factor + compare must consume them directly."""
    import numpy as np

    from conflux_tpu.cli import cholesky_helper
    from conflux_tpu.io import load_matrix_auto

    dim = 32
    rng = np.random.default_rng(0)
    B = rng.standard_normal((dim, dim))
    A = B @ B.T + dim * np.eye(dim)
    raw = tmp_path / "input_32.bin"
    A.astype(np.float64).tofile(str(raw))  # reference format: no header

    np.testing.assert_array_equal(load_matrix_auto(str(raw)), A)

    out = tmp_path / "mine_32.bin"
    rc = cholesky_helper.main(
        ["factor", str(raw), str(out), "--tile", "8", "--platform", "cpu",
         "--devices", "1", "--dtype", "float64"])
    assert rc == 0
    import scipy.linalg

    ref = tmp_path / "result_32.bin"
    L = scipy.linalg.cholesky(A, lower=True)
    L.astype(np.float64).tofile(str(ref))  # raw reference result file
    rc = cholesky_helper.main(
        ["compare", str(out), str(ref), "--lower", "--tol", "1e-10"])
    assert rc == 0


def test_qr_miniapp_tall_and_full(capsys):
    from conflux_tpu.cli import qr_miniapp

    out = run_cli(
        qr_miniapp.main,
        ["-M", "128", "--cols", "16", "-r", "2", "--p_grid", "4,1,1",
         "--validate", "--dtype", "float64"],
        capsys,
    )
    lines = [l for l in out.splitlines() if l.startswith("_result_")]
    assert len(lines) == 2
    assert re.match(
        r"_result_ qr-tsqr,conflux_tpu,128,64,4,4x1x1,time,weak,[\d.]+,16,float64",
        lines[0]), lines[0]
    res = [l for l in out.splitlines() if l.startswith("_residual_")]
    assert "orth=" in res[0]
    assert float(res[0].split("orth=")[1].split()[0]) < 1e-12

    out = run_cli(
        qr_miniapp.main,
        ["-M", "64", "--cols", "64", "--full", "-b", "16", "--p_grid",
         "2,2,1", "-r", "1", "--validate", "--dtype", "float64"],
        capsys,
    )
    assert "_result_ qr,conflux_tpu,64," in out
    res = [l for l in out.splitlines() if l.startswith("_residual_")][0]
    assert float(res.split("reconstruction=")[1]) < 1e-12


def test_qr_miniapp_rejects_wide(capsys):
    from conflux_tpu.cli import qr_miniapp

    with pytest.raises(SystemExit):
        qr_miniapp.main(["-M", "16", "--cols", "32"])


def test_cholesky_miniapp_refine(capsys):
    from conflux_tpu.cli import cholesky_miniapp

    out = run_cli(
        cholesky_miniapp.main,
        ["--dim", "64", "--tile", "16", "--grid", "2,1,1", "--run", "1",
         "--refine", "2"],
        capsys,
    )
    line = [l for l in out.splitlines()
            if l.startswith("_solve_residual_")][0]
    assert "[PASS <=1e-6]" in line, line
    assert float(line.split("rel=")[1].split()[0]) <= 1e-6
    with pytest.raises(SystemExit):
        cholesky_miniapp.main(["--dim", "64", "--tile", "16", "--run", "1",
                               "--refine", "-1"])


def test_conflux_miniapp_refine(capsys):
    from conflux_tpu.cli import conflux_miniapp

    out = run_cli(
        conflux_miniapp.main,
        ["-N", "64", "-b", "16", "--p_grid", "2,1,1", "-r", "1",
         "--refine", "2"],
        capsys,
    )
    line = [l for l in out.splitlines()
            if l.startswith("_solve_residual_")][0]
    assert "[PASS <=1e-6]" in line, line


def test_miniapps_auto_knob_resolution(capsys):
    """--auto resolves un-passed knobs from the measured dispatch table
    (conflux_tpu.autotune) and reports the provenance; explicit flags are
    untouched."""
    out = run_cli(conflux_miniapp.main,
                  ["-N", "128", "-r", "1", "--auto", "--validate"], capsys)
    # CPU sweep rule: tile 256 (N=128 < v is tile-rounded by geometry)
    assert "_auto_ block_size=256" in out
    assert "_auto_provenance_ CPU-mesh sweep" in out
    assert "_result_" in out and "_residual_" in out
    # an explicit flag wins over the table
    out = run_cli(conflux_miniapp.main,
                  ["-N", "128", "-b", "32", "-r", "1", "--auto"], capsys)
    assert "block_size=" not in out.split("_auto_ ")[1].splitlines()[0]
    assert [l for l in out.splitlines()
            if l.startswith("_result_")][0].rsplit(",", 2)[1] == "32"


def test_auto_explicit_default_value_pins(capsys):
    """A flag explicitly passed AT the library default value still pins
    its knob — the table must not silently override it (ADVICE r4 #1:
    sentinel None parser defaults distinguish un-passed from
    passed-at-default)."""
    out = run_cli(conflux_miniapp.main,
                  ["-N", "128", "-b", "128", "-r", "1", "--auto"], capsys)
    # table says 256, but -b 128 (the library default) was explicit
    assert "block_size=" not in out.split("_auto_ ")[1].splitlines()[0]
    assert [l for l in out.splitlines()
            if l.startswith("_result_")][0].rsplit(",", 2)[1] == "128"


def test_auto_without_flag_resolves_library_defaults(capsys):
    """Without --auto, sentinel-None knobs resolve to library defaults
    (the pre-sentinel behavior must be unchanged for plain runs)."""
    out = run_cli(conflux_miniapp.main,
                  ["-N", "128", "-r", "1"], capsys)
    assert [l for l in out.splitlines()
            if l.startswith("_result_")][0].rsplit(",", 2)[1] == "128"
    assert "_auto_" not in out


def test_qr_auto_empty_mode_reports_no_knobs(capsys):
    """qr tall CholeskyQR2 mode has no auto-tunable knobs; --auto must
    say so rather than print "(all knobs pinned)" (ADVICE r4 #4)."""
    from conflux_tpu.cli import qr_miniapp

    out = run_cli(qr_miniapp.main,
                  ["-M", "256", "--cols", "64", "--algo", "cholesky",
                   "-r", "1", "--auto"], capsys)
    assert "_auto_ (no auto-tunable knobs for this mode)" in out
    assert "(all knobs pinned)" not in out
    assert "_auto_provenance_" not in out


def test_platform_tpu_off_tpu_fails_clearly():
    """`--platform tpu` never quietly becomes a CPU run."""
    with pytest.raises(SystemExit, match="not a TPU"):
        conflux_miniapp.main(["--platform", "tpu", "-N", "64", "-b", "16",
                              "-r", "1"])


def _chip_smoke():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("phase", ["lu", "lu_mesh", "cholesky", "serving",
                                   "pallas"])
def test_chip_smoke_phases_tiny(phase, capsys):
    """Every phase of chip_smoke.py, at a size the test picks, on the CPU:
    its line prints PASS under the phase's bound."""
    import jax

    from conflux_tpu.geometry import Grid3

    cs = _chip_smoke()
    devs = jax.devices()
    if phase == "lu":
        cs.phase_lu(devs, 3, n=512, v=128)
    elif phase == "lu_mesh":
        cs.phase_lu(devs, 3, n=512, v=64, grid=Grid3(2, 1, 2), exact=False)
    elif phase == "cholesky":
        cs.phase_cholesky(devs, 3, n=256, v=64)
    elif phase == "serving":
        cs.phase_serving(3, fleet=8, n=64, v=32)
    else:
        cs.phase_pallas(3, sessions=4, n=64, panel_rows=256,
                        require_mosaic=False)
    out = capsys.readouterr().out
    assert "PASS" in out and "FAIL" not in out, out


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_off_chip_exits_nonzero(where, tmp_path):
    """Off the chip, or without the rest of the repo, the script fails
    and never prints a result."""
    import os
    import shutil
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cwd = root
    if where == "alone":
        shutil.copy(os.path.join(root, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
