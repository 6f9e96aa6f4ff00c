"""The production Pallas kernels compile for a TPU v5e chip.

Every other test runs the kernels on the CPU, in interpret mode or with a
jnp stand-in for the Mosaic roll, so none of them lowers a kernel for
the chip. Here each kernel of the sigverify hot path is compiled at the
bench width (batch 8192, msg_len 1232; the counted strict call also at
the verify tile's dispatch width, 1024) for one chip of a described,
not attached, `v5e:2x2` topology: the TPU compiler refuses here what it
would refuse on the chip (an unsupported primitive in the kernel, a
negative roll, too much VMEM). Nothing runs, so this says nothing about
results or times — chip_smoke.py does that on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file. Keep these tests in this one file.
"""
import os

import pytest

B, L = 8192, 1232


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    import jax
    import jax.numpy as jnp
    args = [jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one_chip)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


def _sha512(m, n):
    from firedancer_tpu.ops.pallas_sha import sha512
    return sha512(m, n)


def _verify(sig, pub, msg, ln):
    from firedancer_tpu.ops.pallas_ed import verify_batch
    return verify_batch(sig, pub, msg, ln)


def _verify_counted(sig, pub, msg, ln, n):
    from firedancer_tpu.ops.pallas_ed import verify_batch
    return verify_batch(sig, pub, msg, ln, n_live=n)


def _rlc(sig, pub, msg, ln, z):
    from firedancer_tpu.ops.pallas_msm import rlc_verify_batch_tpu
    return rlc_verify_batch_tpu(sig, pub, msg, ln, z)


def _sig(b):
    return [((b, 64), "uint8"), ((b, 32), "uint8"), ((b, L), "uint8"),
            ((b,), "int32")]


_SIG = _sig(B)


@pytest.mark.parametrize("fn,shapes", [
    (_sha512, [((B, L + 64), "uint8"), ((B,), "int32")]),
    (_verify, _SIG),
    # the live-lane count: scalar prefetch and the program guard
    (_verify_counted, _SIG + [((), "int32")]),
    # the verify tile's dispatch width (tiles/verify.py): one SHA
    # program, four strict-kernel programs
    (_verify_counted, _sig(1024) + [((), "int32")]),
], ids=["pallas_sha.sha512", "pallas_ed.verify_batch",
        "pallas_ed.verify_batch.n_live", "pallas_ed.verify_batch.w1024"])
def test_kernel_compiles_for_v5e(one_chip, no_compile_cache, fn, shapes):
    _compile(one_chip, fn, *shapes)


@pytest.mark.slow   # ~4 min of TPU compile on an 8-core host
@pytest.mark.parametrize("b", [B, 1024], ids=["batch", "w1024"])
def test_rlc_msm_compiles_for_v5e(one_chip, no_compile_cache, b):
    """At the bench width, and at the verify tile's dispatch width, to
    which its prefilter pads each chunk."""
    _compile(one_chip, _rlc, *_sig(b), ((b, 16), "uint8"))
