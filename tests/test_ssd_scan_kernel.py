"""The Mamba-2 (SSD) scan's Pallas kernels (``ops/pallas/ssd_scan.py``) in
Pallas's interpreter on the CPU, at the widths the two cells run (heads of
64, 128 states, chunks of 128 and 256): the forward and every gradient
against the SEQUENTIAL float32 recurrence (``benchmark/
reference_nemotron_h.scan``, a token at a time, a document at a time) and
against the XLA einsums of ``models/ssm.ssd_scan``; rows that are no
multiple of the chunk; documents that end inside a chunk, exactly at a
chunk's end, twice in one chunk, and in trailing padding; more than one
B/C group through the kernel or through the counted fall-back; and the
control no cell's ``correct`` sees — the carried state narrowed to
bfloat16 — which this file must refuse.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import ssm
from areal_tpu.ops.pallas import ssd_scan as kernel
from benchmark import reference_nemotron_h as ref

P, N = 64, 128
GRADS = ("x", "dt", "A", "B", "C")


def inputs(T, H, G, seed=0, dtype=jnp.float32, published=False):
    """One row. ``published``: Δ and A as the published keys draw them
    (Δ log-uniform in [0.001, 0.1], A = -U(1, 16)): the state lasts."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (1, T, H, P)).astype(dtype)
    if published:
        dt = jnp.exp(jax.random.uniform(ks[1], (1, T, H)) * (
            np.log(0.1) - np.log(0.001)) + np.log(0.001))
        A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    else:
        dt = jax.nn.softplus(jax.random.normal(ks[1], (1, T, H)) - 2.0)
        A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7))
    Bm = jax.random.normal(ks[3], (1, T, G, N)).astype(dtype)
    Cm = jax.random.normal(ks[4], (1, T, G, N)).astype(dtype)
    return x, dt, A, Bm, Cm


def layout(which, Q, T):
    """Segment ids [1, T] of a row whose documents end (a) inside a chunk,
    (b) exactly at a chunk's end, (c) twice in one chunk, (d) in trailing
    padding (segment 0) that starts inside a chunk."""
    cuts = {"inside": [Q // 3, T], "at_end": [Q, 2 * Q, T],
            "twice": [Q + 5, Q + 40, T], "padding": [Q - 9, T - Q // 2]}[which]
    seg = np.zeros((1, T), np.int32)
    a = 0
    for i, b in enumerate(cuts):
        seg[0, a:b] = i + 1
        a = b
    return seg


def documents(seg):
    for s in np.unique(seg[0]):
        if s:
            idx = np.nonzero(seg[0] == s)[0]
            yield idx[0], idx[-1] + 1


def sequential(x, dt, A, Bm, Cm, seg, w):
    """(Σ w · y, y [1, T, H, P]) of the recurrence, a document at a time,
    in float32; y is 0 on padding."""
    of_head = np.arange(x.shape[2]) // (x.shape[2] // Bm.shape[2])
    f32 = jnp.float32
    y = jnp.zeros(x.shape, f32)
    for a, e in documents(seg):
        y = y.at[0, a:e].set(ref.scan(
            x[0, a:e].astype(f32), dt[0, a:e], A,
            Bm[0, a:e][:, of_head].astype(f32),
            Cm[0, a:e][:, of_head].astype(f32)))
    return jnp.sum(y * w), y


def chunked(impl, x, dt, A, Bm, Cm, seg, w, Q):
    """The same of ``ssm.ssd_scan`` under ``impl``."""
    y = ssm.ssd_scan(x, dt, A, Bm, Cm, jnp.asarray(seg), Q, impl)
    y = y * (seg > 0)[..., None, None]
    return jnp.sum(y * w), y


def grads_and_y(f, *args):
    """((dx, dΔ, dA, dB, dC), y) of ``f``, one jitted program."""
    (_, y), grads = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return grads, y


def worst(got, want):
    """max |got - want| over max |want|, float32."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


CASES = [  # chunk, heads, groups, layout, row length
    (128, 16, 1, "inside", 300), (128, 16, 2, "at_end", 300),
    (128, 32, 1, "twice", 290), (128, 16, 1, "padding", 333),
    (256, 32, 1, "inside", 600), (256, 16, 2, "at_end", 600),
    (256, 16, 1, "twice", 530), (256, 32, 2, "padding", 640),
]


@pytest.mark.parametrize("Q,H,G,which,T", CASES)
def test_the_kernel_equals_the_sequential_scan_in_float32(Q, H, G, which, T):
    """Forward and the five gradients, compute dtype float32: against the
    recurrence and against the XLA form, at float32's own distance."""
    args = inputs(T, H, G, seed=Q + H + G)
    seg = layout(which, Q, T)
    w = jax.random.normal(jax.random.PRNGKey(9), (1, T, H, P))
    before = ssm.scan_impl_counts().get("pallas_interpret", 0)
    got, y = grads_and_y(
        lambda *a: chunked("pallas_interpret", *a, seg, w, Q), *args)
    assert ssm.scan_impl_counts()["pallas_interpret"] == before + 1
    xla, y_xla = grads_and_y(
        lambda *a: chunked("reference", *a, seg, w, Q), *args)
    want, y_seq = grads_and_y(lambda *a: sequential(*a, seg, w), *args)
    assert worst(y, y_seq) < 2e-5 and worst(y, y_xla) < 2e-5
    for name, g, x_, s in zip(GRADS, got, xla, want):
        assert worst(g, s) < 2e-4, (name, worst(g, s))
        assert worst(g, x_) < 2e-4, (name, worst(g, x_))


@pytest.mark.parametrize("Q,H,G,which,T", [
    (128, 16, 2, "twice", 290), (256, 32, 1, "padding", 600),
    (256, 16, 1, "at_end", 600)])
def test_the_kernel_in_bfloat16_is_as_near_as_the_xla_form(Q, H, G, which, T):
    """x, B and C bfloat16, Δ and A as the published keys draw them: the
    kernel's distance from the float32 recurrence (on the same values) is
    the XLA form's own — the same roundings, placed in the same operands —
    for y and for every gradient. The decays' gradients sum the operands'
    roundings over a whole row and differ most: twice the XLA form's
    distance and a bfloat16 ulp of room."""
    args = inputs(T, H, G, seed=Q + H, dtype=jnp.bfloat16, published=True)
    seg = layout(which, Q, T)
    w = jax.random.normal(jax.random.PRNGKey(9), (1, T, H, P))
    want, y_seq = grads_and_y(lambda *a: sequential(*a, seg, w), *args)
    got, y = grads_and_y(
        lambda *a: chunked("pallas_interpret", *a, seg, w, Q), *args)
    xla, y_xla = grads_and_y(
        lambda *a: chunked("reference", *a, seg, w, Q), *args)
    for name, g, x_, s in zip(GRADS, got, xla, want):
        assert worst(g, s) < 2 * worst(x_, s) + 2 ** -8, (
            name, worst(g, s), worst(x_, s))
    assert y.dtype == jnp.float32
    assert worst(y, y_seq) < 1.5 * worst(y_xla, y_seq) + 1e-3


def test_a_bfloat16_state_between_chunks_is_refused():
    """The control: the recurrence with its state rounded to bfloat16 after
    each chunk's last token — what a kernel with a bfloat16 carry computes
    —, at decays as the published keys draw them (the state lasts many
    chunks), fails the tolerance that the kernel passes; and the states
    the kernel keeps for its backward hold bits bfloat16 has not. No
    cell's ``correct`` would see it (PERF.md §7), so this file does."""
    Q, H, T = 128, 16, 640
    x, dt, A, Bm, Cm = inputs(T, H, 1, seed=5, published=True)
    bf, f32 = jnp.bfloat16, jnp.float32

    def recurrence(narrow):
        def step(S, inp):
            t, x_t, dt_t = inp
            S = (jnp.exp(dt_t * A)[:, None, None] * S
                 + (dt_t[:, None] * x_t)[:, :, None] * Bm[0, t])
            y = jnp.einsum("hpn,n->hp", S, Cm[0, t, 0], precision=ref.HI)
            if narrow:
                S = jnp.where(t % Q == Q - 1, S.astype(bf).astype(f32), S)
            return S, y

        return jax.lax.scan(step, jnp.zeros((H, P, N), f32),
                            (jnp.arange(T), x[0], dt[0]))[1]

    want = jax.jit(recurrence, static_argnums=0)(False)
    y, states = kernel.scan_fwd(x, dt, dt * A, Bm, Cm,
                                jnp.ones((1, T), jnp.int32), Q, keep=True,
                                interpret=True)
    assert worst(y[0], want) < 2e-5
    assert worst(jax.jit(recurrence, static_argnums=0)(True),
                 want) > 2e-4  # the float32 cases' tolerance
    assert states.dtype == f32 and states.shape == (1, T // Q, H * P, N)
    assert worst(states.astype(bf), states) > 2 ** -10


def test_groups_the_kernel_does_not_take_fall_back_and_are_counted():
    """Eight groups of two heads (the published Nemotron's 8 groups): two
    heads are no whole sublane tile, so the scan runs the XLA form, says
    so in ``scan_impl_counts()``, and answers the same."""
    Q, H, G, T = 128, 16, 8, 200
    assert not kernel.supported(Q, H, P, G, N)
    assert kernel.supported(Q, H, P, 2, N) and kernel.supported(256, 32, P, 1,
                                                               N)
    args = inputs(T, H, G)
    seg = jnp.asarray(layout("inside", Q, T))
    before = dict(ssm.scan_impl_counts())
    got = ssm.ssd_scan(*args, seg, Q, "pallas_interpret")
    after = ssm.scan_impl_counts()
    assert after["xla"] == before.get("xla", 0) + 1
    assert after.get("pallas_interpret", 0) == before.get(
        "pallas_interpret", 0)
    np.testing.assert_array_equal(
        got, ssm.ssd_scan(*args, seg, Q, "reference"))
    # ... and widths off the lane grid (the tiny models of the other test
    # files) never reach the kernel
    assert not kernel.supported(8, 8, 16, 1, 16)
    # ... nor a step whose picks would not fit VMEM beside the rest
    assert kernel.supported(Q, 64, P, 4, N)
    assert not kernel.supported(Q, 120, P, 1, N)


@pytest.mark.parametrize("vmem_mib,how", [(16, "xla"), (64, "xla"),
                                          (128, "pallas")])
def test_a_chip_without_the_vmem_the_kernel_asks_for_runs_the_einsums(
        monkeypatch, vmem_mib, how):
    """``impl="pallas"`` on a chip whose VMEM is not twice the kernels'
    ``VMEM_LIMIT`` (v2 to v4: 16 MiB; v5p, v7x: 64): the XLA form, counted
    — no compile error; a v5e's 128 MiB takes the kernel. Here, without a
    TPU, there is no chip to ask and the kernel is taken."""
    import types

    assert kernel.fits_device()
    monkeypatch.setattr(kernel.pltpu, "get_tpu_info", lambda: (
        types.SimpleNamespace(vmem_capacity_bytes=vmem_mib << 20)))
    Q, H, T = 128, 16, 256
    args = inputs(T, H, 1)
    before = ssm.scan_impl_counts().get(how, 0)
    jax.eval_shape(lambda *a: ssm.ssd_scan(
        *a, jnp.ones((1, T), jnp.int32), Q, "pallas"), *args)
    assert ssm.scan_impl_counts()[how] == before + 1


@pytest.mark.parametrize("impl", ["pallas_interpret", "reference"])
def test_the_geometry_key_keeps_five_fields_and_the_true_length(impl):
    """``geometry_counts()`` is the benchmark's contract (its drivers
    format the key with five ``%d`` and reckon the roofline from the TRUE
    row length): whatever runs the scan, one traced call adds one to
    ``(rows, length, chunk, heads, groups)``; ``scan_impl_counts()`` is
    where the implementation shows."""
    Q, H, T = 128, 16, 333  # padded to 384 inside
    args = inputs(T, H, 1)
    seg = jnp.asarray(layout("padding", Q, T))
    key = (1, T, Q, H, 1)
    how = "xla" if impl == "reference" else impl
    before = ssm.geometry_counts().get(key, 0)
    impl_before = ssm.scan_impl_counts().get(how, 0)
    jax.jit(lambda *a: ssm.ssd_scan(*a, seg, Q, impl)).lower(*args)
    assert ssm.geometry_counts()[key] == before + 1
    assert all(len(k) == 5 for k in ssm.geometry_counts())
    assert "%dx%d/%d/h%dg%d" % key == f"1x{T}/{Q}/h{H}g1"
    assert ssm.scan_impl_counts()[how] == impl_before + 1
    assert 0.0 <= ssm.ssd_kernel_frac() <= 1.0


def test_the_kernels_are_lowered_under_the_scans_scope():
    """Lowered for a TPU (no chip needed to lower), the forward and the
    backward kernel are custom calls whose op names the benchmark's own
    reduction (``ssm_trace.scope_of``) files under ``ssm_scan`` — what
    ``*_scan_busy_pct`` and ``*_scan_roofline`` read — also where the
    caller opened no scope itself, and in the backward pass, which a
    custom_vjp traces outside the forward's scope."""
    import re

    from benchmark import ssm_trace

    Q, H, T = 128, 16, 256
    args = inputs(T, H, 1, dtype=jnp.bfloat16)
    seg = jnp.ones((1, T), jnp.int32)

    def loss(*a):
        return jnp.sum(ssm.ssd_scan(*a, seg, Q, "pallas") ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).trace(
        *args).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert text.count("tpu_custom_call") >= 2
    names = re.findall(r'loc\("([^"]*pallas_call)"', text)
    for kernel_name in (kernel.FWD_NAME, kernel.BWD_NAME):
        mine = [n for n in names if f"/{kernel_name}/" in n]
        assert mine, (kernel_name, names)
        assert all(ssm_trace.scope_of(n) == "ssm_scan" for n in mine), mine
