"""The in-batch pre-dedup (``ops.expand.pre_dedup``) and the per-stage
microbenchmark of the staged pipeline (``tools/kernel_bench.py``)."""

import numpy as np
import pytest


class TestPreDedupSoundness:
    """`ops.expand.pre_dedup` arena-collision property: a lane is ONLY
    dropped when an earlier valid lane carries the SAME fingerprint —
    distinct keys colliding on an arena cell must both survive, so the
    retained fingerprint SET always equals the valid input set."""

    @staticmethod
    def _check(chi, clo, cvalid):
        import jax.numpy as jnp

        from stateright_tpu.ops.expand import pre_dedup
        keep = np.asarray(pre_dedup(jnp.asarray(chi), jnp.asarray(clo),
                                    jnp.asarray(cvalid)))
        fps = [(int(h), int(l)) for h, l in zip(chi, clo)]
        valid_set = {fp for fp, v in zip(fps, cvalid) if v}
        kept_set = {fp for fp, k in zip(fps, keep) if k}
        # soundness: no fingerprint vanishes, no invalid lane appears
        assert kept_set == valid_set
        # a dropped lane always has an EARLIER kept duplicate
        for i, (fp, v) in enumerate(zip(fps, cvalid)):
            if v and not keep[i]:
                assert any(keep[j] and fps[j] == fp for j in range(i))
        return keep

    def test_random_batches(self):
        rng = np.random.default_rng(7)
        for n in (8, 64, 257):
            chi = rng.integers(0, 2**32, n, dtype=np.uint32)
            clo = rng.integers(0, 2**32, n, dtype=np.uint32)
            # force heavy duplication: sample lanes from few keys
            pick = rng.integers(0, max(n // 4, 1), n)
            chi, clo = chi[pick], clo[pick]
            cvalid = rng.random(n) < 0.8
            self._check(chi, clo, cvalid)

    def test_engineered_arena_collisions(self):
        # distinct keys crafted onto the SAME arena cell: slot is
        # (clo ^ chi*PHI) & (acells-1) with acells = 2^ceil(log2(2n)),
        # so with chi=0, clo values differing only above the mask bits
        # collide. Both must be kept (dropping either would lose a
        # unique state — unsound).
        n = 8
        acells = 1 << max((2 * n - 1).bit_length(), 0)
        chi = np.zeros(n, np.uint32)
        clo = (np.arange(n, dtype=np.uint32) * np.uint32(acells)
               + np.uint32(3))  # all lanes -> arena cell 3
        cvalid = np.ones(n, bool)
        keep = self._check(chi, clo, cvalid)
        assert keep.all()  # distinct keys: nothing may be dropped

    def test_collision_with_duplicates_mixed(self):
        # colliding distinct keys on one cell (all must survive — a
        # collision loser is only dropped when the winner VERIFIES
        # equal) plus a true-duplicate pair alone on another cell
        # (the later lane dies in favor of the earlier). Duplicates
        # hiding behind a foreign collision winner survive pre-dedup —
        # that's the documented soundness trade; the table probe
        # resolves them.
        n = 16
        acells = 1 << max((2 * n - 1).bit_length(), 0)
        chi = np.zeros(n, np.uint32)
        # lanes 0..11: distinct keys, all on arena cell 3
        clo = (np.arange(n, dtype=np.uint32) * np.uint32(acells)
               + np.uint32(3))
        # lanes 12..15: ONE key on its own cell 7 — true duplicates
        clo[12:] = np.uint32(7)
        cvalid = np.ones(n, bool)
        keep = self._check(chi, clo, cvalid)
        assert keep[:13].all()          # distinct keys + first dup
        assert not keep[13:].any()      # later duplicates die


@pytest.mark.slow
def test_kernel_bench_emits_json(tmp_path):
    # tools/kernel_bench.py: the per-stage microbenchmark of the
    # staged pipeline must land parseable per-stage JSON
    import json
    import subprocess
    import sys
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "kb.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "kernel_bench.py"),
         "--model", "2pc4", "--fmax", "64", "--iters", "2",
         "--out", str(out)],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(out.read_text())
    assert line["backend"] == "cpu"
    for key in ("expand_ms", "hash_ms", "pre_dedup_ms", "probe_ms"):
        assert line["stages"][key] >= 0
    assert line["staged_ms"] > 0
    assert 0 <= line["dup_lane_frac"] <= 1
