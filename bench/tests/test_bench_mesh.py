"""A four-chip l1-logistic cell added as new files only, run on four
virtual CPU devices in a subprocess (the test process keeps one device):
its data is drawn over a (1, 4) mesh with no device making the whole
design, and its path and fit entries run through the program's mesh
driver and come out correct. A webspam-shaped four-chip configuration
committed under bench/configs at its published size shrinks with the
tiny root, and its path and fit cells come out correct too."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, re, sys, tempfile
    from pathlib import Path
    import jax
    import numpy as np
    from bench import run
    from bench.laws import block_ell_sharded, block_ell_uniform
    from bench.tests.conftest import (LOGISTIC_LIMITS, WEBSPAM_CELLS, WEBSPAM_SPEC,
                                      add_logistic_cells, commit_config, copy_root,
                                      make_tiny_root)
    from repro import distributed

    out = {}
    # the law over (1, 4): each device draws its own blocks; p = 5,500 is
    # 22 blocks, padded to 24 on the mesh (6,144 features, 1,536 a device)
    spec = dict(m=300, p=5500, block_size=256, nnz_max=20, col_density=0.02,
                n_relevant=12, coef_scale=10.0, noise=0.05)
    mesh = distributed.fw_mesh(n_data=1, n_model=4)
    key = jax.random.key(2**31 + 11)
    got = block_ell_sharded.generate(spec, key, mesh=mesh)
    want = block_ell_uniform.generate(spec, key)
    nb = want["values"].shape[0]
    out["shape"] = list(got["values"].shape)
    out["shard"] = list(got["values"].sharding.shard_shape(got["values"].shape))
    out["same_as_uniform"] = all(
        np.array_equal(np.asarray(got[k])[0, :nb], np.asarray(want[k])) for k in ("values", "rows")
    ) and all(np.array_equal(np.asarray(got[k]), np.asarray(want[k])) for k in ("y", "coef"))
    out["padding_empty"] = float(np.abs(np.asarray(got["values"])[0, nb:]).sum())
    hlo = block_ell_sharded.program(spec, mesh).lower(key).compile().as_text()
    whole = {24, 24 * 256}
    out["whole_shapes"] = sorted(
        s for s in set(re.findall(r"\\[([0-9,]+)\\]", hlo))
        if whole & {int(d) for d in s.split(",") if d})

    root = Path(tempfile.mkdtemp())
    make_tiny_root(root)
    before = {str(p): p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cells = add_logistic_cells(root, 4)
    for name in cells:
        res = run.run(["--workload", name, "--seed", "3000000041", "--seconds", "0",
                       "--trace", "0"], root=root, require_tpu=False)
        out[name] = {"correct": res["correct"], "attempted": res["attempted"],
                     "failed": res["failed"], "count": res["device"]["count"],
                     "metrics": sorted(res["metrics"]), "checks": res["checks"]}
    out["unchanged"] = all(Path(p).read_bytes() == b for p, b in before.items()
                           if Path(p).name != "BENCHMARK.json")

    # webspam trigram's four-chip configuration committed at its published
    # size into a copy of the root, then shrunk with the rest of the root
    src = copy_root(Path(tempfile.mkdtemp()))
    commit_config(src, WEBSPAM_SPEC, WEBSPAM_CELLS, 4, LOGISTIC_LIMITS)
    tiny = make_tiny_root(Path(tempfile.mkdtemp()), src=src)
    out["webspam_spec"] = json.loads((tiny / "bench/configs/webspam-trigram.json").read_text())
    for name in WEBSPAM_CELLS:
        res = run.run(["--workload", name, "--seed", "3000000043", "--seconds", "0",
                       "--trace", "0"], root=tiny, require_tpu=False)
        out[name] = {"correct": res["correct"], "attempted": res["attempted"],
                     "failed": res["failed"], "count": res["device"]["count"],
                     "metrics": sorted(res["metrics"]), "checks": res["checks"]}
    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def four_devices():
    limit = max(900, int(os.environ.get("REPRO_SUBPROC_TIMEOUT", "0")))
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, timeout=limit,
        cwd=ROOT,
        env={"PYTHONPATH": f"{ROOT}:{ROOT / 'src'}", "PATH": "/usr/bin:/bin",
             # stripped env: pin the backend or PJRT plugin discovery can hang
             "JAX_PLATFORMS": "cpu", "HOME": os.environ.get("HOME", "/tmp"),
             "TMPDIR": os.environ.get("TMPDIR", "/tmp")},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def test_sharded_law_draws_each_devices_share(four_devices):
    r = four_devices
    assert r["shape"] == [1, 24, 256, 20] and r["shard"] == [1, 6, 256, 20]
    assert r["same_as_uniform"] and r["padding_empty"] == 0.0
    # no array of the whole design's feature count in the compiled program
    assert r["whole_shapes"] == []


@pytest.mark.parametrize("entry", ["path", "fit"])
def test_four_chip_logistic_cell_from_new_files_is_correct(four_devices, entry):
    r = four_devices[f"l1logit-4.{entry}"]
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == 4 and r["count"] == 4
    assert r["metrics"] == sorted(["setup_s", f"{entry}_s"])


def test_adding_the_cell_changes_no_existing_file(four_devices):
    assert four_devices["unchanged"]


@pytest.mark.parametrize("entry", ["path", "fit"])
def test_committed_webspam_shaped_four_chip_cell_is_correct(four_devices, entry):
    """A four-chip configuration of law block_ell_sharded committed under
    bench/configs, as the webspam-trigram cells will be, shrinks with the
    tiny root to its law's test sizes, keeps its mesh, and its path
    (traffic path-k194-2lanes) and fit cells run correct on four devices."""
    from bench.laws import block_ell_sharded

    spec = four_devices["webspam_spec"]
    assert spec["mesh"] == {"data": 1, "model": 4}
    assert {k: spec[k] for k in block_ell_sharded.TINY} == block_ell_sharded.TINY
    r = four_devices[f"webspam-trigram.{entry}-k194"]
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == 4 and r["count"] == 4
    assert r["metrics"] == sorted(["setup_s", f"{entry}_s"])
