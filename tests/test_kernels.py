"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle across
shape/dtype sweeps, driven entirely by the KernelSpec registry — each
kernel's spec carries its own cases and tolerances, so a newly registered
kernel is covered with zero edits here."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import api, registry

KEY = jax.random.PRNGKey(0)
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}

CASES = [(spec, case) for spec in registry.all_kernels()
         for case in spec.cases]


def _cast(v, dtype):
    """Cast float inputs to the target dtype; integer inputs (page tables,
    int8 pools) are structural and keep their native dtype."""
    v = jnp.asarray(v)
    return v if jnp.issubdtype(v.dtype, jnp.integer) else v.astype(dtype)


@pytest.mark.parametrize(
    "spec,case", CASES,
    ids=[f"{spec.name}-{i}-{case.dtype}"
         for spec in registry.all_kernels()
         for i, case in enumerate(spec.cases)])
def test_pallas_matches_ref(spec, case):
    inputs = spec.example_inputs(shape=dict(case.shape))
    args = [_cast(v, jnp.float32) for v in inputs.values()]
    want = api.run(spec.name, *args, backend="ref", **dict(case.kwargs))
    argsk = [_cast(a, DTYPES[case.dtype]) for a in args]
    got = api.run(spec.name, *argsk, backend="pallas", tile=dict(case.tile),
                  interpret=True, **dict(case.kwargs))
    tol = spec.tol[case.dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_every_registered_kernel_declares_cases():
    for spec in registry.all_kernels():
        assert spec.cases, spec.name
        assert {c.dtype for c in spec.cases} <= set(spec.dtypes)


def test_model_ssd_chunked_matches_oracle():
    from repro.kernels.ssd_scan import ref as ssd_ref
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(KEY, 4)
    B, S, H, P, G, N = 2, 96, 4, 16, 1, 8
    x = jax.random.normal(ks[0], (B, S, H, P))
    bm = jax.random.normal(ks[1], (B, S, G, N)) * 0.5
    cm = jax.random.normal(ks[2], (B, S, G, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    a = -jnp.exp(jax.random.uniform(KEY, (H,), maxval=1.0))
    want, want_h = ssd_ref.ssd(x, bm, cm, dt, a)
    got, got_h = ssd_chunked(x, bm, cm, dt, a, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_custom_vjp_grads():
    from repro.kernels.flash_attention import ref as flash_ref
    from repro.kernels.flash_attention.ops import flash_attention
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))

    def loss_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 0, 32, 32, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(flash_ref.attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_attention_grads_through_registry_dispatch():
    """api.run must route through the custom-vjp entry (vjp_mode)."""
    assert registry.get("flash_attention").vjp_mode == "custom_vjp"
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))

    def loss_api(q, k, v):
        out = api.run("flash_attention", q, k, v,
                      tile={"block_q": 32, "block_k": 32})
        return jnp.sum(out ** 2)

    def loss_ref(q, k, v):
        from repro.kernels.flash_attention import ref as flash_ref
        return jnp.sum(flash_ref.attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_api, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def _nan_past_live_pages(inputs, k):
    """`example_inputs` made ragged: row 0 is dead (length 1 on the trash
    slot), the other rows take their given lengths; every table entry past
    a row's live pages names a slot of NaN, and every position no query
    row sees, in the trash slot and in each row's last live page, is NaN
    too. Returns (NaN pools, the same pools with zeros there)."""
    from repro.kernels.paged_attention.paged_attention import live_pages
    t = inputs["k_pages"].shape[1]
    table, lengths = inputs["page_table"].copy(), inputs["lengths"].copy()
    nan_slot = min(set(range(inputs["k_pages"].shape[0])) - set(table.flat))
    table[0], lengths[0] = table[0, 0], 1          # row 0's page: the trash
    unseen = np.zeros(inputs["k_pages"].shape[:2], bool)     # (slot, pos)
    for r, (row, n) in enumerate(zip(table, lengths)):
        live = int(live_pages(n, k, t, table.shape[1]))
        row[live:] = nan_slot
        for j, slot in enumerate(row[:live]):
            pos = j * t + np.arange(t)
            unseen[slot] |= pos >= n + k - 1
    unseen[nan_slot] = True
    inputs = {**inputs, "page_table": table, "lengths": lengths}
    clean, poisoned = dict(inputs), dict(inputs)
    for name in ("k_pages", "v_pages", "k_scale", "v_scale"):
        a = inputs[name]
        mask = unseen.reshape(unseen.shape + (1,) * (a.ndim - 2))
        clean[name] = np.where(mask, 0.0, a).astype(a.dtype)
        poisoned[name] = np.where(mask, np.nan, a).astype(a.dtype)
    return poisoned, clean


@pytest.mark.parametrize("k,ppb,slots,stacked", [
    (1, 1, 7, False), (1, 2, 7, False), (1, 4, 7, True),
    (4, 2, 7, False), (4, 4, 6, True), (16, 1, 7, False),
    (16, 2, 7, True), (16, 4, 6, False)])
def test_paged_attention_reads_only_live_pages(k, ppb, slots, stacked,
                                              monkeypatch):
    """Only each row's live pages are copied (six copies a page, counted
    as they start), and what lies past them never reaches the output:
    NaN in every table entry past a row's live pages and in every
    position no query row sees leaves the output finite and equal to the
    reference on clean pools. Lengths of 1, ppb x t and ppb x t + 1, a
    dead row on its trash page, k query rows whose later rows cross a
    page block's edge, mixed-tier pools, tables whose width is not a
    multiple of ppb."""
    from repro.kernels.paged_attention import paged_attention as kernel
    started = []

    class Counted:
        def __init__(self, copy):
            self.copy = copy

        def start(self):
            jax.debug.callback(lambda: started.append(1))
            self.copy.start()

        def wait(self):
            self.copy.wait()

    make = kernel.pltpu.make_async_copy
    monkeypatch.setattr(kernel.pltpu, "make_async_copy",
                        lambda *a, **kw: Counted(make(*a, **kw)))
    spec = registry.get("paged_attention")
    t = 16
    shape = {"b": 6, "pages": 6 * slots + 2, "page_tokens": t,
             "slots": slots, "hq": 4, "hkv": 2, "d": 32, "k": k}
    inputs = spec.example_inputs(shape=shape, seed=k + ppb)
    edge = ppb * t
    want_len = [1, 1, edge, edge + 1, edge - k // 2, slots * t - (k - 1)]
    inputs["lengths"] = np.minimum(want_len, slots * t - (k - 1)) \
        .astype(np.int32)
    poisoned, clean = _nan_past_live_pages(inputs, k)
    names = spec.arg_names

    def args(pools, layered):
        out = [jnp.asarray(pools[n]) for n in names]
        if layered:     # layer 1 of two: layer 0 all NaN
            out = [a if i in (0, 7, 8) else
                   jnp.stack([jnp.full_like(a, jnp.nan) if
                              jnp.issubdtype(a.dtype, jnp.floating)
                              else a, a])
                   for i, a in enumerate(out)] + [jnp.int32(1)]
        return out

    want = api.run(spec.name, *args(clean, False), backend="ref")
    got = kernel.paged_attention_pallas(*args(poisoned, stacked),
                                        pages_per_block=ppb, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    assert len(started) == 6 * kernel.live_pages(
        poisoned["lengths"], k, t, slots).sum()
    tol = spec.tol["float32"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)
