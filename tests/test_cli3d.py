"""3-D driver surface: engines agree, dumps load, validation fires."""

from __future__ import annotations

import numpy as np
import pytest

import jax

from gol_tpu import cli3d, volume

jax.config.update("jax_platforms", "cpu")


def test_parse_rule3d():
    r = cli3d.parse_rule3d("bays4555")
    assert r.birth == frozenset({5}) and r.survive == frozenset({4, 5})
    r = cli3d.parse_rule3d("B5,6/S4,5,26")
    assert r.birth == frozenset({5, 6})
    assert r.survive == frozenset({4, 5, 26})
    with pytest.raises(ValueError, match="malformed"):
        cli3d.parse_rule3d("5/45")
    with pytest.raises(ValueError, match="> 26"):
        cli3d.parse_rule3d("B27/S")


@pytest.mark.parametrize("engine", ["dense", "bitpack"])
def test_engines_agree_on_dump(tmp_path, engine, capsys):
    rc = cli3d.main(
        ["2", "32", "3", "64", "1", "--engine", engine, "--outdir",
         str(tmp_path / engine)]
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert "TOTAL DURATION" in out and "POPULATION" in out


def test_engine_dumps_are_identical(tmp_path):
    for engine in ("dense", "bitpack"):
        assert (
            cli3d.main(
                ["2", "32", "3", "64", "1", "--engine", engine, "--outdir",
                 str(tmp_path / engine)]
            )
            == 0
        )
    a = np.load(tmp_path / "dense" / "World3D_of_1.npy")
    b = np.load(tmp_path / "bitpack" / "World3D_of_1.npy")
    np.testing.assert_array_equal(a, b)


def test_sharded_3d_cli_matches_single(tmp_path):
    assert (
        cli3d.main(
            ["2", "32", "2", "64", "1", "--mesh", "3d", "--outdir",
             str(tmp_path / "mesh")]
        )
        == 0
    )
    assert (
        cli3d.main(
            ["2", "32", "2", "64", "1", "--engine", "dense", "--outdir",
             str(tmp_path / "single")]
        )
        == 0
    )
    np.testing.assert_array_equal(
        np.load(tmp_path / "mesh" / "World3D_of_1.npy"),
        np.load(tmp_path / "single" / "World3D_of_1.npy"),
    )


def test_validation(capsys):
    assert cli3d.main(["9", "16", "1", "64", "0"]) == 255
    assert "not been implemented" in capsys.readouterr().out
    assert cli3d.main(["2", "16", "1", "64", "0", "--rule", "wat"]) == 255
    assert cli3d.main(["2", "16", "1", "0", "0"]) == 255
    assert cli3d.main(["2", "16"]) == 255  # wrong arg count -> usage


def test_zero_iterations(tmp_path, capsys):
    rc = cli3d.main(
        ["1", "16", "0", "64", "1", "--outdir", str(tmp_path)]
    )
    assert rc == 0
    vol = np.load(tmp_path / "World3D_of_1.npy")
    assert vol.sum() == 16**3


def test_sharded_3d_custom_rule(tmp_path):
    """--mesh 3d + a custom rule through the packed sharded path.

    Size 64 over the 2x2x2 mesh gives x-shards 32 cells wide — exactly
    one packed word — so auto takes compiled_evolve3d_packed (size 32
    would silently fall back to the dense sharded engine)."""
    a = cli3d.main(
        ["2", "64", "2", "64", "1", "--mesh", "3d", "--rule", "B5,6/S4,5",
         "--outdir", str(tmp_path / "mesh")]
    )
    b = cli3d.main(
        ["2", "64", "2", "64", "1", "--engine", "dense", "--rule",
         "B5,6/S4,5", "--outdir", str(tmp_path / "single")]
    )
    assert a == 0 and b == 0
    np.testing.assert_array_equal(
        np.load(tmp_path / "mesh" / "World3D_of_1.npy"),
        np.load(tmp_path / "single" / "World3D_of_1.npy"),
    )


# -- checkpoint / resume (capability parity with the 2-D driver) -------------


def test_cli3d_checkpoint_and_resume_equivalence(tmp_path, capsys):
    """10 straight generations == 4 generations + snapshot + resumed 6."""
    from gol_tpu import cli3d
    from gol_tpu.utils import checkpoint as ckpt_mod

    out_a = tmp_path / "a"
    out_b = tmp_path / "b"
    ck = tmp_path / "ck"
    assert cli3d.main(
        ["2", "32", "10", "64", "1", "--outdir", str(out_a)]
    ) == 0
    assert cli3d.main(
        ["2", "32", "4", "64", "0", "--checkpoint-every", "4",
         "--checkpoint-dir", str(ck)]
    ) == 0
    resume = ckpt_mod.checkpoint3d_path(str(ck), 4)
    assert cli3d.main(
        ["2", "32", "6", "64", "1", "--resume", resume,
         "--outdir", str(out_b)]
    ) == 0
    import numpy as np_

    a = np_.load(out_a / "World3D_of_1.npy")
    b = np_.load(out_b / "World3D_of_1.npy")
    np_.testing.assert_array_equal(a, b)


def test_cli3d_resume_rule_mismatch_rejected(tmp_path, capsys):
    from gol_tpu import cli3d
    from gol_tpu.utils import checkpoint as ckpt_mod

    ck = tmp_path / "ck"
    assert cli3d.main(
        ["2", "32", "4", "64", "0", "--checkpoint-every", "4",
         "--checkpoint-dir", str(ck), "--rule", "bays5766"]
    ) == 0
    capsys.readouterr()
    rc = cli3d.main(
        ["2", "32", "2", "64", "0",
         "--resume", ckpt_mod.checkpoint3d_path(str(ck), 4)]
    )
    assert rc == 255
    assert "pass the matching --rule" in capsys.readouterr().out


def test_cli3d_resume_corrupt_snapshot_rejected(tmp_path, capsys):
    import numpy as np_

    from gol_tpu import cli3d
    from gol_tpu.utils import checkpoint as ckpt_mod

    path = ckpt_mod.checkpoint3d_path(str(tmp_path), 3)
    vol = np_.random.default_rng(0).integers(0, 2, (32, 32, 32), np_.uint8)
    ckpt_mod.save3d(path, vol, 3, "B5/S4,5")
    with np_.load(path) as data:
        arrays = {k: data[k].copy() for k in data.files}
    arrays["volume"][0, 0, 0] ^= 1  # in-range flip
    np_.savez_compressed(path, **arrays)
    capsys.readouterr()
    rc = cli3d.main(["2", "32", "2", "64", "0", "--resume", path])
    assert rc == 255
    assert "corrupt" in capsys.readouterr().out


def test_cli3d_resume_missing_file_fails_clean(tmp_path, capsys):
    from gol_tpu import cli3d

    rc = cli3d.main(
        ["2", "32", "2", "64", "0", "--resume", str(tmp_path / "nope.npz")]
    )
    assert rc == 255  # OSError path: clean message, no traceback


def test_cli3d_resume_2d_checkpoint_rejected(tmp_path, capsys):
    import numpy as np_

    from gol_tpu import cli3d
    from gol_tpu.utils import checkpoint as ckpt_mod

    path = ckpt_mod.checkpoint_path(str(tmp_path), 1)
    ckpt_mod.save(path, np_.zeros((8, 8), np_.uint8), 1, num_ranks=1)
    capsys.readouterr()
    rc = cli3d.main(["2", "32", "2", "64", "0", "--resume", path])
    assert rc == 255
    assert "not a 3-D snapshot" in capsys.readouterr().out


def test_cli3d_resume_truncated_snapshot_fails_clean(tmp_path, capsys):
    from gol_tpu import cli3d

    bad = tmp_path / "trunc.gol3d.npz"
    bad.write_bytes(b"PK\x03\x04 definitely not a real zip")
    rc = cli3d.main(["2", "32", "2", "64", "0", "--resume", str(bad)])
    assert rc == 255
    assert "not a readable snapshot" in capsys.readouterr().out


def test_cli3d_resume_missing_fingerprint_fails_clean(tmp_path, capsys):
    import numpy as np_

    from gol_tpu import cli3d

    bad = tmp_path / "nofp.gol3d.npz"
    np_.savez_compressed(
        bad, volume=np_.zeros((32, 32, 32), np_.uint8)
    )
    rc = cli3d.main(["2", "32", "2", "64", "0", "--resume", str(bad)])
    assert rc == 255
    assert "missing" in capsys.readouterr().out

def test_mesh_pallas_engine_matches_single_device(tmp_path, capsys):
    """--engine pallas --mesh 3d (H-unsharded shape): the fused sharded
    kernel per shard, byte-compared against the single-device dump."""
    rc = cli3d.main(
        ["2", "128", "10", "64", "1", "--mesh", "3d", "--mesh-shape",
         "2,1,4", "--engine", "pallas", "--outdir", str(tmp_path / "a")]
    )
    assert rc == 0, capsys.readouterr().out
    rc = cli3d.main(
        ["2", "128", "10", "64", "1", "--engine", "bitpack", "--outdir",
         str(tmp_path / "b")]
    )
    assert rc == 0
    a = np.load(tmp_path / "a" / "World3D_of_1.npy")
    b = np.load(tmp_path / "b" / "World3D_of_1.npy")
    np.testing.assert_array_equal(a, b)


def test_mesh_pallas_engine_rejects_sharded_h(capsys):
    rc = cli3d.main(
        ["2", "64", "2", "64", "0", "--mesh", "3d", "--mesh-shape",
         "2,2,2", "--engine", "pallas"]
    )
    assert rc == 255
    assert "H-unsharded" in capsys.readouterr().out


def test_mesh_shape_validation(capsys):
    rc = cli3d.main(
        ["2", "32", "1", "64", "0", "--mesh-shape", "2,1,4"]
    )
    assert rc == 255
    assert "--mesh 3d" in capsys.readouterr().out
    rc = cli3d.main(
        ["2", "32", "1", "64", "0", "--mesh", "3d", "--mesh-shape", "nope"]
    )
    assert rc == 255
    assert "P,R,C" in capsys.readouterr().out

# -- round-3 driver parity: sharded checkpoints, guard, resume ---------------


def test_sharded_checkpoint_and_resume_byte_exact(tmp_path, capsys):
    """Mesh run writes the sharded piece-file format (no monolithic npz,
    no host gather); resume from it == straight run, byte-exact."""

    common = ["2", "64", "10", "64", "1", "--mesh", "3d", "--mesh-shape",
              "2,1,2", "--engine", "bitpack"]
    rc = cli3d.main(common + ["--outdir", str(tmp_path / "straight")])
    assert rc == 0

    rc = cli3d.main(
        ["2", "64", "4", "64", "0", "--mesh", "3d", "--mesh-shape",
         "2,1,2", "--engine", "bitpack", "--checkpoint-every", "4",
         "--checkpoint-dir", str(tmp_path / "ck")]
    )
    assert rc == 0, capsys.readouterr().out
    ckdir = tmp_path / "ck" / "ckpt3d_000000000004.gol3d.d"
    assert ckdir.is_dir()  # the sharded format, not a monolithic npz
    assert (ckdir / "manifest.npz").exists()
    rc = cli3d.main(
        ["2", "64", "6", "64", "1", "--mesh", "3d", "--mesh-shape",
         "2,1,2", "--engine", "bitpack", "--resume", str(ckdir),
         "--outdir", str(tmp_path / "resumed")]
    )
    assert rc == 0, capsys.readouterr().out
    a = np.load(tmp_path / "straight" / "World3D_of_1.npy")
    b = np.load(tmp_path / "resumed" / "World3D_of_1.npy")
    np.testing.assert_array_equal(a, b)
    # Single-device resume from the same sharded checkpoint.
    rc = cli3d.main(
        ["2", "64", "6", "64", "1", "--engine", "bitpack", "--resume",
         str(ckdir), "--outdir", str(tmp_path / "resumed1")]
    )
    assert rc == 0, capsys.readouterr().out
    c = np.load(tmp_path / "resumed1" / "World3D_of_1.npy")
    np.testing.assert_array_equal(a, c)


def test_guarded_run_matches_unguarded(tmp_path, capsys):
    rc = cli3d.main(
        ["2", "32", "9", "64", "1", "--engine", "bitpack",
         "--guard-every", "4", "--outdir", str(tmp_path / "g")]
    )
    assert rc == 0, capsys.readouterr().out
    out = capsys.readouterr().out
    assert "GUARD          : 3 checks, 0 failures, 0 restores" in out
    rc = cli3d.main(
        ["2", "32", "9", "64", "1", "--engine", "bitpack",
         "--outdir", str(tmp_path / "p")]
    )
    assert rc == 0
    np.testing.assert_array_equal(
        np.load(tmp_path / "g" / "World3D_of_1.npy"),
        np.load(tmp_path / "p" / "World3D_of_1.npy"),
    )


def test_guarded_redundant_run(tmp_path, capsys):
    rc = cli3d.main(
        ["2", "32", "8", "64", "1", "--engine", "bitpack",
         "--guard-every", "4", "--guard-redundant",
         "--outdir", str(tmp_path / "r")]
    )
    assert rc == 0, capsys.readouterr().out
    assert "GUARD          : 2 checks" in capsys.readouterr().out
    rc = cli3d.main(
        ["2", "32", "8", "64", "1", "--engine", "dense",
         "--outdir", str(tmp_path / "p")]
    )
    assert rc == 0
    np.testing.assert_array_equal(
        np.load(tmp_path / "r" / "World3D_of_1.npy"),
        np.load(tmp_path / "p" / "World3D_of_1.npy"),
    )


def test_guard_redundant_requires_guard_every(capsys):
    rc = cli3d.main(["2", "32", "4", "64", "0", "--guard-redundant"])
    assert rc == 255
    assert "--guard-every" in capsys.readouterr().out


def test_guard3d_fault_drill():
    """guarded_loop + the 3-D driver's evolvers: an out-of-range flip is
    detected and rolled back; an in-range flip needs the redundant audit."""
    import jax.numpy as jnp

    from gol_tpu.ops import life3d
    from gol_tpu.utils import guard as guard_mod
    from gol_tpu.utils.timing import Stopwatch

    size, rule = 32, cli3d.parse_rule3d("bays4555")
    vol = cli3d.init_volume(2, size)
    compiled, place = volume._build_evolver("bitpack", None, 4, rule, size)
    evolvers = {4: (compiled, ())}
    fired = []

    def hook(board, gen):
        if gen == 8 and not fired:
            fired.append(gen)
            return board.at[1, 2, 3].set(jnp.uint8(0xA5))  # out-of-range
        return board

    sw, rep = Stopwatch(), guard_mod.GuardReport()
    board, generation = guard_mod.guarded_loop(
        sw, rep, place(vol), 0, [4, 4, 4], evolvers, None,
        guard_mod.GuardConfig(check_every=4, fault_hook=hook),
    )
    assert generation == 12
    assert rep.failures == 1 and rep.restores == 1 and rep.checks == 4
    ref = jnp.asarray(vol)
    for _ in range(12):
        ref = life3d.step3d(ref)
    np.testing.assert_array_equal(np.asarray(board), np.asarray(ref))


def test_guard3d_redundant_catches_inrange_flip():
    import jax.numpy as jnp

    from gol_tpu.utils import guard as guard_mod
    from gol_tpu.utils.timing import Stopwatch

    size, rule = 32, cli3d.parse_rule3d("bays4555")
    vol = cli3d.init_volume(2, size)
    evolvers = {
        4: (volume._build_evolver("bitpack", None, 4, rule, size)[0], ())
    }
    checkers = {
        4: (volume._build_evolver("dense", None, 4, rule, size)[0], ())
    }
    fired = []

    def hook(board, gen):
        if gen == 4 and not fired:
            fired.append(gen)
            v = int(board[0, 0, 0])
            return board.at[0, 0, 0].set(jnp.uint8(1 - v))  # IN-range
        return board

    sw, rep = Stopwatch(), guard_mod.GuardReport()
    import jax

    board, generation = guard_mod.guarded_loop(
        sw, rep, jax.device_put(vol), 0, [4, 4], evolvers, checkers,
        guard_mod.GuardConfig(check_every=4, fault_hook=hook, redundant=True),
    )
    assert generation == 8
    assert rep.failures == 1 and rep.restores == 1

def test_resume_from_2d_sharded_dir_clean_error(tmp_path, capsys):
    """Pointing --resume at a 2-D sharded checkpoint dir must exit 255
    with a clean cross-driver message, not a KeyError traceback."""
    import jax
    import jax.numpy as jnp

    from gol_tpu.parallel import mesh as mesh_mod
    from gol_tpu.utils import checkpoint as ckpt_mod

    mesh = mesh_mod.make_mesh_1d(4)
    board = jax.device_put(
        jnp.zeros((32, 32), jnp.uint8), mesh_mod.board_sharding(mesh)
    )
    d = ckpt_mod.sharded_checkpoint_path(str(tmp_path), 3)
    ckpt_mod.save_sharded(d, board, 3, num_ranks=4)
    rc = cli3d.main(
        ["2", "32", "2", "64", "0", "--engine", "dense", "--resume", d]
    )
    assert rc == 255
    assert "3-D sharded checkpoint manifest" in capsys.readouterr().out
