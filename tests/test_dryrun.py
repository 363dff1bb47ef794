"""Sharded lowering smoke (deliverable e, reduced scale).

The production dry-run needs 512 forced host devices, which must be set
before jax initializes — so these tests run ``repro.launch.dryrun`` machinery
in a SUBPROCESS with a smaller forced device count and reduced configs.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, timeout=900) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )


@pytest.mark.slow
def test_reduced_dryrun_all_kinds_on_8_devices():
    """Every step kind (train/prefill/decode) lowers + compiles on a 2x4 mesh
    with reduced configs, through the exact production code path."""
    proc = run_py(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, dataclasses, jax
        from repro.configs import INPUT_SHAPES
        from repro.configs.reduced import reduced_config
        from repro.launch.dryrun import lower_case
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("data", "model"))
        out = {}
        cases = [
            ("internlm2-1.8b", "train_4k"),
            ("granite-moe-3b-a800m", "train_4k"),
            ("xlstm-1.3b", "prefill_32k"),
            ("hymba-1.5b", "decode_32k"),
        ]
        for arch, shape_name in cases:
            cfg = reduced_config(arch)
            shape = INPUT_SHAPES[shape_name]
            small = dataclasses.replace(
                shape, seq_len=128, global_batch=8
            )
            import repro.launch.dryrun as DR
            orig = DR.INPUT_SHAPES[shape_name]
            DR.INPUT_SHAPES[shape_name] = small
            try:
                lowered, meta = lower_case(arch, shape_name, mesh=mesh, cfg=cfg)
                compiled = lowered.compile()
                ca = compiled.cost_analysis()
                out[f"{arch}/{shape_name}"] = float(ca.get("flops", -1.0))
            finally:
                DR.INPUT_SHAPES[shape_name] = orig
        print("RESULT::" + json.dumps(out))
        """
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT::")][0]
    res = json.loads(line[len("RESULT::"):])
    assert len(res) == 4
    for k, flops in res.items():
        assert flops > 0, k


@pytest.mark.slow
def test_production_mesh_shapes():
    proc = run_py(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        import jax
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        print("RESULT::", m1.devices.shape, m1.axis_names, m2.devices.shape, m2.axis_names)
        """
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = [l for l in proc.stdout.splitlines() if l.startswith("RESULT::")][0]
    assert "(16, 16)" in out and "('data', 'model')" in out
    assert "(2, 16, 16)" in out and "('pod', 'data', 'model')" in out


def test_dryrun_results_file_covers_all_pairs():
    """The committed dry-run artifact must cover 10 archs x 4 shapes x 2
    meshes with no errors (deliverable e evidence)."""
    path = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun_results.jsonl")
    assert os.path.exists(path), "run: PYTHONPATH=src python -m repro.launch.dryrun"
    rows = [json.loads(l) for l in open(path)]
    pairs = {(r["arch"], r["shape"], r["mesh"]) for r in rows}
    assert len(pairs) >= 80
    archs = {r["arch"] for r in rows}
    assert len(archs) == 10
    for r in rows:
        assert "error" not in r, r.get("arch")
        assert r["compute_s"] > 0
        assert r["bottleneck"] in ("compute", "memory", "collective")


@pytest.mark.slow
def test_train_launcher_subprocess():
    """The distributed training launcher runs sharded steps end to end."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "internlm2-1.8b",
         "--reduced", "--steps", "4", "--host-devices", "8", "--mesh", "2x4",
         "--log-every", "2"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "loss" in proc.stdout


@pytest.mark.slow
def test_serve_launcher_subprocess():
    """The serving launcher compiles two configs and switches between them."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "internlm2-1.8b",
         "--reduced", "--host-devices", "8", "--mesh", "2x4", "--batch", "4",
         "--prompt-len", "32", "--window", "16", "--tokens", "9"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "switch accurate -> fast" in proc.stdout
    assert "decoded 9 tokens" in proc.stdout
