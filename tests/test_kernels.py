"""Pallas kernels (interpret mode) vs pure-jnp oracles, with shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, quant, ref
from repro.kernels import paged_attention as pa
from repro.models import attention as A


class TestStreamedMatmul:
    @given(
        m=st.sampled_from([32, 64, 128]),
        k=st.sampled_from([32, 64, 128]),
        n=st.sampled_from([32, 64, 96]),
        bm=st.sampled_from([16, 32]),
        dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
    )
    @settings(max_examples=20, deadline=None)
    def test_sweep_vs_ref(self, m, k, n, bm, dtype):
        x = jax.random.normal(jax.random.PRNGKey(m + n), (m, k), dtype)
        y = jax.random.normal(jax.random.PRNGKey(k), (k, n), dtype)
        out = ops.matmul(x, y, block_m=bm, block_n=16, block_k=16)
        want = ref.matmul_ref(x, y)
        tol = 1e-4 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol * 8)

    def test_block_shape_invariance(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (128, 128))
        y = jax.random.normal(jax.random.PRNGKey(1), (128, 128))
        outs = [
            np.asarray(ops.matmul(x, y, block_m=bm, block_n=bn, block_k=bk))
            for bm, bn, bk in [(32, 32, 32), (64, 64, 64), (128, 128, 128)]
        ]
        for o in outs[1:]:
            # rtol alone is meaningless for near-zero entries of a random
            # matmul; bound the absolute f32 accumulation-order difference
            np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-4)


class TestFlashKernel:
    @pytest.mark.parametrize("kw", [
        dict(causal=True), dict(causal=False), dict(causal=True, window=48),
        dict(causal=True, softcap=20.0),
    ])
    def test_vs_oracle(self, kw):
        b, s, h, hkv, hd = 2, 128, 4, 2, 32
        q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, hd))
        k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, hd))
        v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, hd))
        out = ops.flash_attention(q, k, v, block_q=32, block_k=32, **kw)
        kw2 = {("softcap_val" if k_ == "softcap" else k_): v_ for k_, v_ in kw.items()}
        want = A.naive_attention(q, k, v, **kw2)
        np.testing.assert_allclose(out, want, atol=2e-5)

    @given(
        s=st.sampled_from([64, 128]),
        bq=st.sampled_from([16, 32, 64]),
        hd=st.sampled_from([16, 32]),
        dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
    )
    @settings(max_examples=15, deadline=None)
    def test_sweep(self, s, bq, hd, dtype):
        q = jax.random.normal(jax.random.PRNGKey(s), (1, s, 2, hd), dtype)
        k = jax.random.normal(jax.random.PRNGKey(s + 1), (1, s, 2, hd), dtype)
        v = jax.random.normal(jax.random.PRNGKey(s + 2), (1, s, 2, hd), dtype)
        out = ops.flash_attention(q, k, v, block_q=bq, block_k=bq, causal=True)
        want = A.naive_attention(q, k, v, causal=True)
        tol = 3e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32), atol=tol)


class TestPagedAttentionKernel:
    def _case(self, seed, b, hkv, g, hd, nb, bs, n_pages):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        q = jax.random.normal(ks[0], (b, hkv * g, hd))
        kp = jax.random.normal(ks[1], (nb, bs, hkv, hd))
        vp = jax.random.normal(ks[2], (nb, bs, hkv, hd))
        # distinct physical pages per row (rows share none, like the pool)
        perm = jax.random.permutation(ks[3], nb - 1)[: b * n_pages] + 1
        pt = perm.reshape(b, n_pages).astype(jnp.int32)
        cl = jax.random.randint(ks[4], (b,), 0, n_pages * bs)
        return q, kp, vp, pt, cl

    @pytest.mark.parametrize("kw", [
        dict(), dict(window=11), dict(softcap=20.0),
        dict(window=7, softcap=15.0),
    ])
    def test_vs_oracle(self, kw):
        q, kp, vp, pt, cl = self._case(0, b=3, hkv=2, g=2, hd=16, nb=16,
                                       bs=8, n_pages=4)
        out = ops.paged_attention(q, kp, vp, pt, cl, scale=0.25, **kw)
        want = ref.paged_attention_ref(q, kp, vp, pt, cl, scale=0.25, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_matches_model_gather_path(self):
        """Kernel == the model's pure-JAX gather reference
        (paged_decode_attention), i.e. the two engine decode paths agree."""
        q, kp, vp, pt, cl = self._case(7, b=2, hkv=2, g=1, hd=16, nb=9,
                                       bs=8, n_pages=4)
        want = A.paged_decode_attention(
            q[:, None], kp, vp, pt, cur_len=cl, scale=0.25)[:, 0]
        out = ops.paged_attention(q, kp, vp, pt, cl, scale=0.25)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @given(
        bs=st.sampled_from([4, 8, 16]),
        n_pages=st.sampled_from([2, 4]),
        g=st.sampled_from([1, 2, 4]),
    )
    @settings(max_examples=10, deadline=None)
    def test_sweep(self, bs, n_pages, g):
        q, kp, vp, pt, cl = self._case(
            bs * 10 + n_pages, b=2, hkv=2, g=g, hd=16,
            nb=2 * n_pages + 2, bs=bs, n_pages=n_pages)
        out = ops.paged_attention(q, kp, vp, pt, cl, scale=0.25)
        want = ref.paged_attention_ref(q, kp, vp, pt, cl, scale=0.25)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_default_rule_spans_blocks(self):
        """The P the shapes give (``pages_per_block``) with a table of
        several blocks: 16-row pages, 37 of them."""
        bs, n = 16, 37
        p = pa.pages_per_block(bs, n)
        assert 1 < p < n and n % p
        ks = jax.random.split(jax.random.PRNGKey(2), 4)
        q = jax.random.normal(ks[0], (3, 4, 16))
        kp = jax.random.normal(ks[1], (3 * n + 1, bs, 2, 16))
        vp = jax.random.normal(ks[2], (3 * n + 1, bs, 2, 16))
        pt = (1 + jax.random.permutation(ks[3], 3 * n)).reshape(3, n)
        cl = jnp.array([p * bs - 1, p * bs, n * bs - 1], jnp.int32)
        out = ops.paged_attention(q, kp, vp, pt.astype(jnp.int32), cl,
                                  scale=0.25)
        want = ref.paged_attention_ref(q, kp, vp, pt, cl, scale=0.25)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


class TestPagedAttentionBlocks:
    """P pages per grid step: the table spans several compute blocks, the
    last one partial, and a block copies only the pages live for its row.
    Rows sit at the block edges: ``cur_len`` 0, the last position of block
    0 (P * bs - 1), the first of block 1 (P * bs), in the table's last
    page, and a free row whose whole table points at the trash page."""

    BS, N_PAGES, P = 4, 10, 3  # blocks of 3, 3, 3 and 1 page
    HKV, G, HD = 2, 2, 16

    @pytest.fixture(autouse=True)
    def _blocks_of_p_pages(self, monkeypatch):
        monkeypatch.setattr(pa, "KEYS_PER_BLOCK", self.P * self.BS)

    def _case(self, seed, t):
        bs, n, p = self.BS, self.N_PAGES, self.P
        cur = np.array([0, p * bs - 1, p * bs, n * bs - 2, 0], np.int32)
        b = len(cur)
        nb = 1 + (b - 1) * n
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        shape = (b, t, self.HKV * self.G, self.HD) if t > 1 else (
            b, self.HKV * self.G, self.HD)
        q = jax.random.normal(ks[0], shape)
        kp = jax.random.normal(ks[1], (nb, bs, self.HKV, self.HD))
        vp = jax.random.normal(ks[2], (nb, bs, self.HKV, self.HD))
        pt = np.zeros((b, n), np.int32)  # page 0 is the trash page
        pt[:-1] = 1 + np.asarray(jax.random.permutation(ks[3], nb - 1)
                                 ).reshape(b - 1, n)
        return q, kp, vp, jnp.asarray(pt), jnp.asarray(cur)

    @pytest.mark.parametrize("kw", [
        dict(), dict(window=9), dict(softcap=15.0),
        dict(window=6, softcap=10.0),
    ])
    @pytest.mark.parametrize("variant", ["plain", "multi", "int8", "fp8"])
    def test_vs_oracle(self, variant, kw):
        t = 3 if variant == "multi" else 1
        q, kp, vp, pt, cl = self._case(11, t)
        scales = {}
        if variant in ("int8", "fp8"):
            ksc, vsc = quant.scales_of(kp, variant), quant.scales_of(vp, variant)
            kp, vp = quant.quantize(kp, ksc, variant), quant.quantize(
                vp, vsc, variant)
            scales = dict(k_scale=ksc, v_scale=vsc)
            want = ref.paged_attention_quant_ref(
                q, kp, vp, ksc, vsc, pt, cl, scale=0.25, **kw)
        elif t > 1:
            want = ref.paged_attention_multi_ref(
                q, kp, vp, pt, cl, scale=0.25, **kw)
        else:
            want = ref.paged_attention_ref(q, kp, vp, pt, cl, scale=0.25, **kw)
        kern = (pa.paged_attention_multi_kernel if t > 1
                else pa.paged_attention_kernel)
        out = kern(q, kp, vp, pt, cl, scale=0.25, interpret=True,
                   **scales, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("t", [1, 3])
    def test_matches_model_gather_path(self, t):
        q, kp, vp, pt, cl = self._case(5, t)
        qt = q if t > 1 else q[:, None]
        want = A.paged_decode_attention(qt, kp, vp, pt, cur_len=cl,
                                        scale=0.25)
        kern = (pa.paged_attention_multi_kernel if t > 1
                else pa.paged_attention_kernel)
        out = kern(qt if t > 1 else q, kp, vp, pt, cl, scale=0.25,
                   interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want if t > 1 else want[:, 0]),
            atol=2e-5, rtol=2e-5)


class TestPagedAttentionStackedPools:
    """The model hands the kernel its stacked pools ``(layers, num_blocks,
    ...)`` and a layer index; the kernel's copies read that layer's pages
    in place. Each layer holds other values, so a copy from the wrong
    layer shows. Same blocks and rows as the class above."""

    BS, N_PAGES, P = 4, 10, 3
    HKV, G, HD = 2, 2, 16
    LAYERS = 3
    _blocks_of_p_pages = TestPagedAttentionBlocks._blocks_of_p_pages
    _case = TestPagedAttentionBlocks._case

    def _stack(self, seed, t, variant):
        q, kp, vp, pt, cl = self._case(seed, t)
        ks = jax.random.split(jax.random.PRNGKey(seed + 1), 2)
        kp = jnp.stack([kp] + [jax.random.normal(k, kp.shape) for k in
                               jax.random.split(ks[0], self.LAYERS - 1)])
        vp = jnp.stack([vp] + [jax.random.normal(k, vp.shape) for k in
                               jax.random.split(ks[1], self.LAYERS - 1)])
        scales = {}
        if variant in ("int8", "fp8"):
            ksc = quant.scales_of(kp, variant)
            vsc = quant.scales_of(vp, variant)
            kp = quant.quantize(kp, ksc, variant)
            vp = quant.quantize(vp, vsc, variant)
            scales = dict(k_scale=ksc, v_scale=vsc)
        return q, kp, vp, pt, cl, scales

    @pytest.mark.parametrize("layer", [0, LAYERS - 1])
    @pytest.mark.parametrize("variant", ["plain", "multi", "int8", "fp8"])
    def test_matches_model_gather_path(self, variant, layer):
        t = 3 if variant == "multi" else 1
        q, kp, vp, pt, cl, scales = self._stack(13, t, variant)
        qt = q if t > 1 else q[:, None]
        want = A.paged_decode_attention(qt, kp, vp, pt, cur_len=cl,
                                        scale=0.25, layer=layer, **scales)
        kern = (pa.paged_attention_multi_kernel if t > 1
                else pa.paged_attention_kernel)
        out = kern(q, kp, vp, pt, cl, scale=0.25, interpret=True,
                   layer=jnp.int32(layer), **scales)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want if t > 1 else want[:, 0]),
            atol=2e-5, rtol=2e-5)
        # The layer's own pools, unstacked, give the same bits.
        one = {k: v[layer] for k, v in scales.items()}
        alone = kern(q, kp[layer], vp[layer], pt, cl, scale=0.25,
                     interpret=True, **one)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(alone))

    @pytest.mark.parametrize("layer", [0, LAYERS - 1])
    @pytest.mark.parametrize("variant", ["plain", "multi", "int8", "fp8"])
    def test_ops_vs_oracle(self, variant, layer):
        t = 3 if variant == "multi" else 1
        q, kp, vp, pt, cl, scales = self._stack(17, t, variant)
        name = {"plain": "paged_attention", "multi": "paged_attention_multi",
                }.get(variant, "paged_attention_quant")
        args = (q, kp, vp) + ((scales["k_scale"], scales["v_scale"])
                              if scales else ()) + (pt, cl)
        out = getattr(ops, name)(*args, scale=0.25, layer=jnp.int32(layer))
        want = getattr(ref, name + "_ref")(*args, scale=0.25, layer=layer)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


class TestPagedAttentionMultiKernel:
    """q_len>1 decode variant (speculative verify): per-query causal cut
    inside the draft block, same page stream as the single-token kernel."""

    def _case(self, seed, b, hkv, g, hd, nb, bs, n_pages, t):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        q = jax.random.normal(ks[0], (b, t, hkv * g, hd))
        kp = jax.random.normal(ks[1], (nb, bs, hkv, hd))
        vp = jax.random.normal(ks[2], (nb, bs, hkv, hd))
        perm = jax.random.permutation(ks[3], nb - 1)[: b * n_pages] + 1
        pt = perm.reshape(b, n_pages).astype(jnp.int32)
        cl = jax.random.randint(ks[4], (b,), 0, n_pages * bs - t)
        return q, kp, vp, pt, cl

    @pytest.mark.parametrize("kw", [
        dict(), dict(window=11), dict(softcap=20.0),
        dict(window=7, softcap=15.0),
    ])
    def test_vs_oracle(self, kw):
        q, kp, vp, pt, cl = self._case(0, b=3, hkv=2, g=2, hd=16, nb=16,
                                       bs=8, n_pages=4, t=5)
        out = ops.paged_attention_multi(q, kp, vp, pt, cl, scale=0.25, **kw)
        want = ref.paged_attention_multi_ref(
            q, kp, vp, pt, cl, scale=0.25, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_matches_model_gather_path(self):
        """Kernel == the model's pure-JAX gather reference at q_len>1,
        i.e. the two multi-token engine decode paths agree."""
        q, kp, vp, pt, cl = self._case(7, b=2, hkv=2, g=1, hd=16, nb=9,
                                       bs=8, n_pages=4, t=3)
        want = A.paged_decode_attention(q, kp, vp, pt, cur_len=cl, scale=0.25)
        out = ops.paged_attention_multi(q, kp, vp, pt, cl, scale=0.25)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_first_row_matches_single_token_kernel(self):
        """Query 0 of a draft block sees exactly what the single-token
        kernel sees: the two kernels agree on the shared position."""
        q, kp, vp, pt, cl = self._case(3, b=2, hkv=2, g=2, hd=16, nb=12,
                                       bs=8, n_pages=4, t=4)
        multi = ops.paged_attention_multi(q, kp, vp, pt, cl, scale=0.25)
        single = ops.paged_attention(q[:, 0], kp, vp, pt, cl, scale=0.25)
        np.testing.assert_allclose(np.asarray(multi[:, 0]),
                                   np.asarray(single),
                                   atol=2e-5, rtol=2e-5)

    @given(
        bs=st.sampled_from([4, 8]),
        t=st.sampled_from([2, 3, 6]),
        g=st.sampled_from([1, 2]),
    )
    @settings(max_examples=8, deadline=None)
    def test_sweep(self, bs, t, g):
        q, kp, vp, pt, cl = self._case(
            bs * 10 + t, b=2, hkv=2, g=g, hd=16, nb=10, bs=bs,
            n_pages=4, t=t)
        out = ops.paged_attention_multi(q, kp, vp, pt, cl, scale=0.25)
        want = ref.paged_attention_multi_ref(
            q, kp, vp, pt, cl, scale=0.25)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


class TestFWT:
    @given(logn=st.integers(4, 13), block=st.sampled_from([16, 64, 256]))
    @settings(max_examples=20, deadline=None)
    def test_flat_sweep(self, logn, block):
        n = 2 ** logn
        x = jax.random.normal(jax.random.PRNGKey(n), (n,))
        out = ops.fwt(x, block=min(block, n))
        want = ref.fwt_ref(x)
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(
            np.asarray(out) / scale, np.asarray(want) / scale, atol=1e-5)

    def test_involution(self):
        """WHT(WHT(x)) == n * x — transform property check."""
        n = 1024
        x = jax.random.normal(jax.random.PRNGKey(5), (n,))
        twice = ops.fwt(ops.fwt(x, block=64), block=64)
        np.testing.assert_allclose(np.asarray(twice) / n, np.asarray(x),
                                   atol=1e-4)

    def test_batched_rows(self):
        x = jax.random.normal(jax.random.PRNGKey(9), (8, 128))
        out = ops.fwt(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref.fwt_ref(x)),
                                   atol=1e-4)


class TestNW:
    @given(b=st.sampled_from([8, 16, 32]), gap=st.sampled_from([0.5, 1.0, 2.0]))
    @settings(max_examples=15, deadline=None)
    def test_tile_sweep(self, b, gap):
        rng = np.random.default_rng(b)
        north = rng.normal(size=b).astype(np.float32)
        west = rng.normal(size=b).astype(np.float32)
        corner = float(rng.normal())
        sub = rng.normal(size=(b, b)).astype(np.float32)
        out = ops.nw_tile(jnp.asarray(north), jnp.asarray(west),
                          jnp.asarray(corner), jnp.asarray(sub), gap=gap)
        want = ref.nw_ref(north, west, corner, sub, gap=gap)
        np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)

    def test_full_wavefront(self):
        rng = np.random.default_rng(0)
        scores = rng.normal(size=(64, 48)).astype(np.float32)
        out = ops.nw_wavefront(jnp.asarray(scores), block=16)
        want = ref.nw_full_ref(scores)
        np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)


class TestSSDChunkKernel:
    @given(
        s=st.sampled_from([32, 64]),
        chunk=st.sampled_from([8, 16, 32]),
        h=st.sampled_from([1, 3]),
        p=st.sampled_from([4, 8]),
    )
    @settings(max_examples=12, deadline=None)
    def test_vs_recurrence(self, s, chunk, h, p):
        from repro.models import mamba
        ks = jax.random.split(jax.random.PRNGKey(s + chunk), 4)
        x = 0.3 * jax.random.normal(ks[0], (2, s, h, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (2, s, h)))
        a = -jnp.exp(jnp.linspace(-1.0, 1.0, h))
        b_ = 0.3 * jax.random.normal(ks[2], (2, s, 16))
        c_ = 0.3 * jax.random.normal(ks[3], (2, s, 16))
        y_k = ops.ssd(x, dt, a, b_, c_, chunk=chunk)
        y_r, _ = mamba.ssd_ref(x, dt, a, b_, c_)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=1e-4)

    def test_state_stays_in_vmem(self):
        """The kernel's inter-chunk state is VMEM scratch: the jaxpr must not
        thread an (N, P) state through HBM-visible outputs."""
        from repro.kernels import ssd_chunk
        xdt = jnp.ones((2, 32, 8))
        adt = -0.1 * jnp.ones((2, 32))
        b_ = jnp.ones((2, 32, 16))
        out = ssd_chunk.ssd_chunk_kernel(xdt, adt, b_, b_, chunk=8, interpret=True)
        assert out.shape == (2, 32, 8)  # only y comes back
