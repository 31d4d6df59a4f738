"""chip_smoke.py on the CPU: its tiny interpret-mode rehearsal passes,
it refuses a backend that is not the chip, a failed check is a non-zero
exit -- and the rule that places both compile caches."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600,
                          **kw)


def _result_line(stdout):
    """The contract's last line, or None when no result was printed."""
    lines = stdout.strip().splitlines()
    if not lines or not lines[-1].startswith('{"ok"'):
        return None
    return json.loads(lines[-1])


class TestChipSmoke:
    def test_rehearsal_passes_with_both_phases(self):
        proc = _run([SMOKE, "--rehearse"])
        assert proc.returncode == 0, proc.stderr[-3000:]
        out = proc.stdout
        assert out.startswith('[smoke] {"platform": "cpu"')
        assert '[smoke] {"trainer": ' in out
        assert '[smoke] {"server": ' in out
        # kernels ran through the interpret hook and were seen routed
        assert '["layer_norm", [64, 128], true]' in out
        assert '["xent", [64, 256], true]' in out
        assert '"fallback_reason": null' in out
        res = _result_line(out)
        assert res == {"ok": True, "device": res["device"]}
        assert res["device"]["platform"] == "cpu"

    def test_cpu_without_rehearse_names_the_platform(self):
        proc = _run([SMOKE])
        assert proc.returncode != 0
        assert "platform 'cpu'" in proc.stderr
        assert _result_line(proc.stdout) is None
        assert "[trainer]" not in proc.stdout

    def test_failed_check_exits_nonzero(self):
        plant = ("import sys, chip_smoke; "
                 "chip_smoke.losses_ok = lambda losses: False; "
                 "sys.exit(chip_smoke.main(['--rehearse']))")
        proc = _run(["-c", plant])
        assert proc.returncode != 0
        assert "chip_smoke check failed: losses" in proc.stderr
        assert _result_line(proc.stdout) is None


class TestCacheRule:
    """core/compile_cache.cache_root: one rule for JAX's persistent
    cache and the repository's own executable cache."""

    @pytest.fixture(autouse=True)
    def _default_flag(self, monkeypatch):
        from paddle_tpu.flags import FLAGS

        # conftest routes the flag to tmp_path; the rule under test is
        # the default, relative value
        monkeypatch.setitem(FLAGS._values, "compile_cache_dir",
                            "paddle_tpu_exe")

    def test_env_var_places_both_and_nothing_sets_a_dir(
            self, monkeypatch, tmp_path):
        import jax

        from paddle_tpu.core import compile_cache as cc

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: calls.append(a))
        assert cc.enable_persistent_cache() == str(tmp_path)
        assert not [a for a in calls
                    if a and a[0] == "jax_compilation_cache_dir"]
        assert cc.exe_cache_root() == str(tmp_path / "paddle_tpu_exe")

    def test_unset_is_one_fixed_path_from_any_directory(
            self, monkeypatch, tmp_path):
        from paddle_tpu.core import compile_cache as cc

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        seen = set()
        for sub in ("a", "b"):
            (tmp_path / sub).mkdir()
            monkeypatch.chdir(tmp_path / sub)
            seen.add((cc.cache_root(), cc.exe_cache_root()))
        root = os.path.join(REPO, ".jax_cache")
        assert seen == {(root, os.path.join(root, "paddle_tpu_exe"))}


def test_token_ids_never_ride_a_float_matmul():
    """Found by the first chip run: the radix admission scattered the
    token history through a float32 one-hot matmul, exact on the CPU
    but rounded to bf16 at the TPU's default precision (6532 -> 6528).
    The CPU cannot reproduce the rounding, so pin the structure: the
    token feeds of the radix and n-gram admissions are never cast to a
    float type."""
    from paddle_tpu import unique_name
    from paddle_tpu.models import transformer as T
    from paddle_tpu.models.decode_engine import CacheConfig, DraftConfig

    kw = dict(seq_len=8, max_out_len=16, d_model=32, n_heads=2,
              n_layers=1, d_inner=64, vocab=64, n_slots=2,
              admit_buckets=[2])
    with unique_name.guard():
        paged = T.build_decode_step_program(
            cache=CacheConfig(layout="paged", block_size=8, n_blocks=6,
                              n_prompt_entries=2), **kw)
    with unique_name.guard():
        ngram = T.build_decode_step_program(
            draft=DraftConfig(k=2, kind="ngram", ngram=2), **kw)
    for prog, feed in ((paged.serves[("radix", 2)], "hist_toks"),
                       (ngram.serves[2], "src_ids")):
        for block in prog.blocks:
            for op in block.ops:
                if op.type == "cast" and feed in op.input_arg_names:
                    out = block._find_var_recursive(
                        op.output_arg_names[0])
                    assert "float" not in str(out.dtype.value), \
                        (feed, op.output_arg_names)
        assert any(feed in op.input_arg_names
                   for block in prog.blocks for op in block.ops), feed


def test_mosaic_kernels_route_to_references_on_a_mesh(monkeypatch):
    """Found by the first four-chip run: XLA cannot partition a Mosaic
    custom call, so a program GSPMD splits over a mesh must trace the
    jnp references (ops/pallas.auto_partitioned, entered by
    core/executor._build_step_fn(on_mesh=True))."""
    import jax

    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.pallas import layer_norm as ln

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas.on_tpu() and ln.usable(256, 512)
    with pallas.auto_partitioned():
        assert not pallas.on_tpu() and not ln.usable(256, 512)
        with pallas.auto_partitioned(False):  # a nested single-device
            assert pallas.on_tpu()            # program is unaffected
    assert pallas.on_tpu()
