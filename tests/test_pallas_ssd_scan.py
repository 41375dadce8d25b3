"""`ops.pallas_ssd_scan` -- the Mamba-2 scan's two kernels.

The kernels run in Pallas' interpreter on the CPU (the same kernel code the
chip compiles; `tests/test_chip_compile.py` asks Mosaic) through the one entry
point `ops.ssd_scan.ssd_scan`, against its XLA formulation and against the
plain recurrence over events (the benchmark's float32 `lax.scan`): the output
and the gradients in ``x``, ``dt``, ``a``, ``B``, ``C`` and the skip ``D``, in
float32 to 2e-5 of the reference's scale and in bfloat16 to the 2e-2 `chip_smoke.py` holds
bfloat16 kernels to. Rows of three chunks of 128 events, two ``B``/``C``
groups of 8 heads of 64, a state of 128: the smallest shape with every lane
offset the cell's has.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_twotower_ep16 as ref
from eventstreamgpt_tpu.ops.pallas_ssd_scan import ssd_scan_applies
from eventstreamgpt_tpu.ops.ssd_scan import ssd_scan

pytestmark = pytest.mark.pallas

B, S, H, P, G, N, CHUNK = 2, 384, 16, 64, 2, 128, 128
# A segment start inside a chunk (70, 200), on a chunk's edge, a segment over three chunks beside single events.
LAYOUTS = {
    None: [[0] * S, [0] * S],
    "inside": [[0] * 70 + [1] * 130 + [2] * 184, [0] * 200 + [1] * 184],
    "edge": [[0] * 128 + [1] * 128 + [2] * 128, [0] * 256 + [1] * 128],
    "mixed": [[0] * 1 + [1] * 1 + [2] * 126 + [3] * 256, [0] * 3 + [1] * 380 + [2] * 1],
    "padded_tail": [[0] * 150 + [1] * 234, [0] * 90 + [1] * 257 + [-1] * 37],  # -1: padding slots
    "ragged": [[0] * 70 + [1] * 230, [0] * 300],  # 300 events: no whole number of chunks
}


def case(layout, dtype, seed=0):
    seg = np.asarray(LAYOUTS[layout], np.int32)
    rows, s = seg.shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    real = jnp.asarray(seg >= 0)
    x = jnp.where(real[..., None, None], jax.random.normal(keys[0], (rows, s, H, P)), 0).astype(dtype)
    # steps of about 0.1 at decays of about -1: a state fades over some ten events, so a chunk's start matters
    dt = jnp.where(real[..., None], jax.nn.softplus(jax.random.normal(keys[1], (rows, s, H)) - 2.0), 0.0)
    a = -jnp.exp(0.5 * jax.random.normal(keys[2], (H,)))
    bmat, cmat = (0.3 * jax.random.normal(k, (rows, s, G, N)) for k in keys[3:5])
    weigh = jax.random.normal(keys[5], x.shape)
    # a padding slot is a segment of its own, as `models.state_space.segment_ordinal` counts them
    first = np.ones(seg.shape, bool)
    first[:, 1:] = (seg[:, 1:] != seg[:, :-1]) | (seg[:, 1:] < 0)
    first = jnp.asarray(first)
    skip = 1.0 + 0.3 * jax.random.normal(keys[6], (H,))
    ordinal = jnp.cumsum(first, axis=1, dtype=jnp.int32)
    return (x, dt, a, bmat.astype(dtype), cmat.astype(dtype), skip), ordinal, first, weigh


def value_and_grads(fn, operands, weigh):
    def loss(*v):
        y = fn(*v)
        return jnp.sum(y.astype(jnp.float32) * weigh), y

    (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4, 5), has_aux=True)(*operands)
    return (y, *grads)


def assert_close(got, want, tol, what):
    for name, g, w in zip(("y", "dx", "ddt", "da", "dB", "dC", "dD"), got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        scale = np.abs(w).max()
        assert scale > 0, name
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol * scale, err_msg=f"{name} against {what}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("layout", list(LAYOUTS), ids=[str(k) for k in LAYOUTS])
def test_kernels_agree_with_the_xla_formulation_and_the_recurrence(layout, dtype, monkeypatch):
    operands, ordinal, first, weigh = case(layout, dtype)
    through = lambda *v: ssd_scan(*v[:5], ordinal, chunk=CHUNK, skip=v[5])  # noqa: E731
    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")
    kernels = value_and_grads(through, operands, weigh)
    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "xla")
    xla = value_and_grads(through, operands, weigh)
    in_float32 = tuple(v.astype(jnp.float32) for v in operands)
    plain = lambda x, dt, a, b, c, skip: ref.recurrence(x, dt, a, b, c, first) + skip[:, None] * x  # noqa: E731
    recurrence = value_and_grads(plain, in_float32, weigh)
    assert kernels[0].dtype == dtype and kernels[0].shape == operands[0].shape
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert_close(kernels, recurrence, tol, "the recurrence")
    assert_close(kernels, xla, tol, "the XLA formulation")


@pytest.mark.parametrize("layout", ["inside", "edge", "mixed"])
def test_a_packed_segment_gives_through_the_kernels_what_it_gives_alone(layout, monkeypatch):
    """Nothing of one subject reaches the next, wherever in a chunk it starts:
    a segment cut out of its row and scanned alone (through the kernels too
    where it is a chunk long, through XLA's products where shorter) gives the
    packed row's outputs."""
    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")
    (x, dt, a, bmat, cmat, _), ordinal, _, _ = case(layout, jnp.float32, seed=1)
    packed = ssd_scan(x, dt, a, bmat, cmat, ordinal, chunk=CHUNK)
    for row, ids in enumerate(LAYOUTS[layout]):
        for s in sorted(set(ids)):
            lo, hi = ids.index(s), len(ids) - ids[::-1].index(s)
            cut = lambda v: v[row : row + 1, lo:hi]  # noqa: E731
            alone = ssd_scan(cut(x), cut(dt), a, cut(bmat), cut(cmat), jnp.zeros((1, hi - lo), jnp.int32), chunk=CHUNK)
            np.testing.assert_allclose(packed[row : row + 1, lo:hi], alone, rtol=2e-5, atol=2e-5)
    unpacked = ssd_scan(x, dt, a, bmat, cmat, jnp.zeros_like(ordinal), chunk=CHUNK)
    last = LAYOUTS[layout][0].index(max(LAYOUTS[layout][0]))
    assert float(jnp.abs(unpacked[0, last:] - packed[0, last:]).max()) > 1e-3


def test_shapes_the_kernels_take():
    assert ssd_scan_applies(128, 64, 64, 8, 128)  # the cell's
    assert ssd_scan_applies(256, 16, 64, 2, 256) and ssd_scan_applies(128, 2, 128, 2, 128)
    assert not ssd_scan_applies(8, 4, 8, 2, 16)  # the tiny test models'
    assert not ssd_scan_applies(64, 64, 64, 8, 128)  # a chunk shorter than a lane tile
    assert not ssd_scan_applies(128, 64, 64, 64, 128)  # a head alone is half a tile
    assert not ssd_scan_applies(128, 64, 64, 8, 64)


def test_a_shape_the_kernels_refuse_takes_xlas_products_with_one_warning_on_a_tpu(monkeypatch):
    """A chunk of 8 events over heads of 8: on a TPU backend `ssd_scan` says
    once that XLA's products run; with the interpreter asked for (tier-1's
    tiny models) and on any other backend it says nothing. The numbers are the
    XLA formulation's either way."""
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    rows, s, heads, p, groups, n = 2, 24, 4, 8, 2, 16
    x = jax.random.normal(keys[0], (rows, s, heads, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (rows, s, heads)))
    a = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    bmat, cmat = (jax.random.normal(k, (rows, s, groups, n)) for k in keys[3:])
    ordinal = jnp.asarray(np.repeat([[1] * 5 + [2] * 8 + [3] * 11], rows, axis=0), jnp.int32)
    first = jnp.concatenate([jnp.ones((rows, 1), bool), ordinal[:, 1:] != ordinal[:, :-1]], axis=1)
    want = ref.recurrence(x, dt, a, bmat, cmat, first)

    def scan_and_its_warnings():
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            got = ssd_scan(x, dt, a, bmat, cmat, ordinal, chunk=8)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        return [w for w in caught if "state-space scan is taking XLA's batched products" in str(w.message)]

    monkeypatch.delenv("ESGPT_PALLAS_IMPL", raising=False)
    assert scan_and_its_warnings() == []  # the CPU: the XLA formulation is what is asked for
    monkeypatch.setenv("ESGPT_PALLAS_IMPL", "pallas_interpret")
    assert scan_and_its_warnings() == []
    monkeypatch.delenv("ESGPT_PALLAS_IMPL")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    (warning,) = scan_and_its_warnings()
    assert "chunk 8, 4 heads of 8 in 2 groups, state 16" in str(warning.message)
