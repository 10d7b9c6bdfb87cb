"""Paged KV cache + continuous batching (ISSUE-4 acceptance sweep).

Covers: paged-vs-dense decode equivalence at kernel level (GQA shapes,
sliding window, shared-pool MLA dv slicing, shuffled non-contiguous
pages) and at model level (GQA and MLA decode steps vs the dense
``generate`` path, jnp ref AND forced-Pallas interpret); the
``paged_partition_counts`` oracle vs in-kernel counters; allocator
alloc/free/fragmentation invariants; ragged-prompt chunked prefill
(padded-chunk path for attention, exact-remainder for recurrent/SWA);
and the engine trace (FIFO admission, per-step retirement, page-leak
freedom, admission control under a scarce pool).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels.decode_attention import (
    decode_attention,
    paged_decode_attention,
    paged_partition_counts,
)
from repro.models import layers, transformer as tf
from repro.models.layers import causal_mask, paged_decode_attend_ref, softmax_attend
from repro.serve import kv_cache
from repro.serve.engine import ServingEngine, latency_stats
from repro.serve.step import generate, make_prefill_step, make_serve_step

KEY = jax.random.PRNGKey(0)
I = dict(interpret=True)


def _paginate(k_dense, v_dense, kv_lens, page_size, num_pages, seed=0):
    """Scatter per-sequence dense K/V rows into a SHUFFLED page pool;
    returns (k_pages, v_pages, block_tables)."""
    b, t, hkv, d = k_dense.shape
    dv = v_dense.shape[-1]
    max_pp = t // page_size
    rng = np.random.default_rng(seed)
    perm = rng.permutation(num_pages)
    kp = np.zeros((hkv, num_pages, page_size, d), np.float32)
    vp = np.zeros((hkv, num_pages, page_size, dv), np.float32)
    bt = -np.ones((b, max_pp), np.int32)
    nxt = 0
    for i in range(b):
        for p in range(kv_cache.pages_for(int(kv_lens[i]), page_size)):
            page = int(perm[nxt]); nxt += 1
            bt[i, p] = page
            lo = p * page_size
            kp[:, page] = np.asarray(k_dense[i, lo:lo + page_size]).transpose(1, 0, 2)
            vp[:, page] = np.asarray(v_dense[i, lo:lo + page_size]).transpose(1, 0, 2)
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt)


class TestPagedKernel:
    @pytest.mark.parametrize("window", [0, 20])
    def test_matches_dense_reference(self, window):
        b, t, h, hkv, d, pg = 3, 96, 8, 4, 16, 8
        kv_lens = np.array([5, 49, 96], np.int32)
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, 1, h, d))
        kd = jax.random.normal(ks[1], (b, t, hkv, d))
        vd = jax.random.normal(ks[2], (b, t, hkv, d))
        kp, vp, bt = _paginate(kd, vd, kv_lens, pg, 48)
        got = paged_decode_attention(q, kp, vp, bt, jnp.asarray(kv_lens),
                                     window=window, **I)
        for i in range(b):
            mask = causal_mask(1, t, window=window,
                               q_offset=int(kv_lens[i]) - 1)
            want = softmax_attend(q[i:i+1], kd[i:i+1], vd[i:i+1], mask)
            np.testing.assert_allclose(np.asarray(got[i:i+1]),
                                       np.asarray(want), atol=1e-5)
        # the jnp fallback agrees too (it is what serve_step runs on CPU)
        ref = paged_decode_attend_ref(q, kp, vp, bt, jnp.asarray(kv_lens),
                                      window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)

    def test_mla_shared_pool_dv_slice(self):
        """MLA serves keys [c_kv | k_rope] and values c_kv from ONE pool:
        v_pages IS k_pages with dv reading the leading columns."""
        b, t, h, r, dr, pg = 2, 64, 4, 24, 8, 8
        kv_lens = np.array([17, 50], np.int32)
        ks = jax.random.split(KEY, 2)
        q = jax.random.normal(ks[0], (b, 1, h, r + dr))
        rows = jax.random.normal(ks[1], (b, t, 1, r + dr))
        kp, _, bt = _paginate(rows, rows, kv_lens, pg, 16)
        got = paged_decode_attention(q, kp, kp, bt, jnp.asarray(kv_lens),
                                     dv=r, **I)
        for i in range(b):
            mask = causal_mask(1, t, q_offset=int(kv_lens[i]) - 1)
            want = softmax_attend(q[i:i+1], rows[i:i+1],
                                  rows[i:i+1, :, :, :r], mask)
            np.testing.assert_allclose(np.asarray(got[i:i+1]),
                                       np.asarray(want), atol=1e-5)

    def test_counts_match_oracle_and_track_fill(self):
        """Acceptance: per-sequence cost is O(own kv_len) — the kernel's
        execution counters equal the analytic oracle at every fill."""
        b, t, h, hkv, d, pg = 4, 128, 4, 2, 16, 16
        kv_lens = np.array([1, 33, 64, 128], np.int32)
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, 1, h, d))
        kd = jax.random.normal(ks[1], (b, t, hkv, d))
        vd = jax.random.normal(ks[2], (b, t, hkv, d))
        kp, vp, bt = _paginate(kd, vd, kv_lens, pg, b * t // pg)
        _, counts = paged_decode_attention(
            q, kp, vp, bt, jnp.asarray(kv_lens), return_counts=True, **I)
        got = np.asarray(counts)[:, 0].sum(axis=1).tolist()
        want, total = paged_partition_counts(t // pg, kv_lens, page_size=pg)
        assert got == want == [1, 3, 4, 8]
        assert total == t // pg
        # every kv-head skips identically
        np.testing.assert_array_equal(
            np.asarray(counts),
            np.broadcast_to(np.asarray(counts)[:, :1], counts.shape))

    def test_inactive_slots_emit_zeros(self):
        b, t, h, d, pg = 2, 32, 4, 16, 8
        q = jax.random.normal(KEY, (b, 1, h, d))
        kp = jax.random.normal(KEY, (h, 8, pg, d))
        bt = jnp.full((b, t // pg), -1, jnp.int32)
        out = paged_decode_attention(q, kp, kp, bt,
                                     jnp.zeros((b,), jnp.int32), **I)
        assert float(jnp.abs(out).max()) == 0.0

    def test_traced_lens_under_jit(self):
        b, t, h, d, pg = 2, 64, 4, 16, 8
        kv_lens = np.array([9, 40], np.int32)
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, 1, h, d))
        kd = jax.random.normal(ks[1], (b, t, h, d))
        vd = jax.random.normal(ks[2], (b, t, h, d))
        kp, vp, bt = _paginate(kd, vd, kv_lens, pg, 16)
        f = jax.jit(lambda q, kp, vp, bt, l: paged_decode_attention(
            q, kp, vp, bt, l, **I))
        got = f(q, kp, vp, bt, jnp.asarray(kv_lens))
        want = paged_decode_attend_ref(q, kp, vp, bt, jnp.asarray(kv_lens))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


class TestPageAllocator:
    def test_alloc_free_roundtrip(self):
        a = kv_cache.PageAllocator(8)
        p1, p2 = a.alloc(3), a.alloc(2)
        assert a.num_free == 3 and a.num_live == 5
        assert len(set(p1) | set(p2)) == 5  # all distinct
        a.free(p1)
        assert a.num_free == 6
        a.free(p2)
        assert a.num_free == 8 and a.num_live == 0

    def test_exhaustion_is_all_or_nothing(self):
        a = kv_cache.PageAllocator(4)
        a.alloc(3)
        with pytest.raises(MemoryError):
            a.alloc(2)
        assert a.num_free == 1  # the failed alloc handed nothing out

    def test_double_free_rejected(self):
        a = kv_cache.PageAllocator(4)
        p = a.alloc(2)
        a.free(p)
        with pytest.raises(ValueError):
            a.free(p)
        with pytest.raises(ValueError):
            a.free([99])

    def test_fragmentation_interleaved_churn(self):
        """Interleaved alloc/free keeps exact accounting and never hands
        out a live page (free-list discipline under fragmentation)."""
        a = kv_cache.PageAllocator(16)
        rng = np.random.default_rng(0)
        held = []
        for _ in range(200):
            if held and rng.random() < 0.45:
                a.free(held.pop(rng.integers(len(held))))
            else:
                n = int(rng.integers(1, 4))
                if a.can_alloc(n):
                    held.append(a.alloc(n))
            live = [p for h in held for p in h]
            assert len(live) == len(set(live)) == a.num_live
            assert a.num_free + a.num_live == 16

    def test_pages_for(self):
        assert kv_cache.pages_for(1, 8) == 1
        assert kv_cache.pages_for(8, 8) == 1
        assert kv_cache.pages_for(9, 8) == 2


class TestPoolWriteRows:
    """``pool_write_rows`` (a row scatter on the pool's flat view) writes
    the same pool, bit for bit, as the 4-D ``.at[:, page, slot]`` scatter
    it replaced, in every way the program calls it.  No block table maps
    page 0, so page 0 of every head must come back untouched: a dropped
    row of head h sent to ``(h*N + N)*P + slot`` would land there."""

    HKV, N, PG, D = 3, 7, 4, 8

    def _pool(self, seed):
        shape = (self.HKV, self.N, self.PG, self.D)
        return jax.random.normal(jax.random.PRNGKey(seed), shape,
                                 jnp.bfloat16)

    def _decode(self, bt, lens, s):
        """A decode (S=1) or verify (S>1) step's write, coordinates from
        the attention layer's own ``_paged_token_coords``."""
        from repro.models.attention import _paged_token_coords

        pool = self._pool(1)
        page, slot, _ = _paged_token_coords(
            {"block_tables": jnp.asarray(bt, jnp.int32),
             "len": jnp.asarray(lens, jnp.int32), "k_pages": pool},
            "k_pages", s)
        rows = jax.random.normal(jax.random.PRNGKey(2),
                                 (self.HKV,) + page.shape + (self.D,),
                                 jnp.bfloat16)
        got = kv_cache.pool_write_rows(pool, rows, page, slot)
        want = pool.at[:, page, slot].set(rows, mode="drop")
        return pool, got, want

    def _prompt(self, block_row, t, n, row0_pos, row_lo):
        """``write_prompt_pages`` against the 4-D scatter at the
        coordinates its docstring gives."""
        pool = {"k_pages": self._pool(3), "v_pages": self._pool(4)}
        ks = jax.random.split(jax.random.PRNGKey(5), 2)
        dense = {x: jax.random.normal(k, (1, 1, t, self.HKV, self.D),
                                      jnp.bfloat16)
                 for x, k in zip(("k", "v"), ks)}
        block_row = jnp.asarray(block_row, jnp.int32)
        got = kv_cache.write_prompt_pages(
            [pool], dense, block_row, jnp.int32(n), jnp.int32(row0_pos),
            jnp.int32(row_lo))[0]
        pos = jnp.arange(t) + row0_pos
        page = block_row[jnp.clip(pos // self.PG, 0, block_row.shape[0] - 1)]
        valid = (pos >= 0) & (pos >= row_lo) & (pos < n) & (page >= 0)
        page = jnp.where(valid, page, self.N)
        slot = pos % self.PG
        want = {x: pool[f"{x}_pages"].at[:, page, slot].set(
            dense[x][0, 0].transpose(1, 0, 2), mode="drop")
            for x in ("k", "v")}
        return (jnp.stack([pool["k_pages"], pool["v_pages"]]),
                jnp.stack([got["k_pages"], got["v_pages"]]),
                jnp.stack([want["k"], want["v"]]))

    @pytest.mark.parametrize("case", [
        "decode_inactive_slots", "verify_past_block_table",
        "prompt_pad_rows_row_lo", "prompt_rolling_row0_neg"])
    def test_matches_4d_scatter_and_drops_stay_drops(self, case):
        if case == "decode_inactive_slots":
            # slots 1 and 3 inactive (-1 rows); the others mid-page
            bt = [[2, 3, -1], [-1, -1, -1], [5, -1, -1], [-1, -1, -1]]
            before, got, want = self._decode(bt, [5, 0, 3, 0], 1)
        elif case == "verify_past_block_table":
            # slot 0 runs off its 3-page table (positions 11, 12, 13),
            # slot 1's last page is unmapped, slot 2 is inactive
            bt = [[2, 6, 4], [1, 5, -1], [-1, -1, -1]]
            before, got, want = self._decode(bt, [11, 6, 0], 3)
        elif case == "prompt_pad_rows_row_lo":
            # 9 live rows of a 16-row bucket; rows < 4 sit in a shared
            # prefix page; the table's tail is unmapped
            before, got, want = self._prompt([2, 5, 3, -1], 16, 9, 0, 4)
        else:
            # SWA rolling buffer of 8 rows holding a 6-token prompt: its
            # first two rows are positions -2 and -1, never written
            before, got, want = self._prompt([6, 1, -1], 8, 6, -2, 0)
        assert got.dtype == before.dtype and got.shape == before.shape
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        assert not np.array_equal(np.asarray(got, np.float32),
                                  np.asarray(before, np.float32))
        np.testing.assert_array_equal(np.asarray(got[..., 0, :, :], np.float32),
                                      np.asarray(before[..., 0, :, :],
                                                 np.float32))


class TestPagedModelDecode:
    """Model-level acceptance: batched paged decode at MIXED per-sequence
    lengths reproduces the dense ``generate`` path token-for-token."""

    def _run_paged(self, cfg, params, prompts, new, max_len, pg):
        b = len(prompts)
        caches = tf.init_caches(cfg, b, max_len, jnp.float32,
                                cache_layout="paged", page_size=pg)
        alloc = kv_cache.PageAllocator(b * kv_cache.pages_for(max_len, pg))
        bt = np.full((b, kv_cache.pages_for(max_len, pg)), -1, np.int32)
        lens = np.zeros((b,), np.int32)
        prefill = make_prefill_step(cfg, chunk=max_len)
        blocks, toks = caches["blocks"], []
        for i, pr in enumerate(prompts):
            n = pr.shape[1]
            pages = alloc.alloc(kv_cache.pages_for(n + new, pg))
            bt[i, :len(pages)] = pages
            dense = tf.init_caches(cfg, 1, 32, jnp.float32)
            t0, dense = prefill(params, pr, dense)
            blocks = kv_cache.write_prompt_pages(
                blocks, dense["blocks"], jnp.asarray(bt[i]), n)
            lens[i] = n
            toks.append(int(t0[0]))
        step = make_serve_step(cfg)
        out = [[t] for t in toks]
        tok = jnp.asarray(np.array(toks)[:, None])
        caches = {"blocks": blocks, "block_tables": jnp.asarray(bt),
                  "lens": jnp.asarray(lens)}
        for _ in range(new - 1):
            tok, caches = step(params, tok, caches)
            for i in range(b):
                out[i].append(int(tok[i, 0]))
        return out

    @pytest.mark.parametrize("arch", ["qwen3_0p6b", "deepseek_v2_236b"])
    def test_paged_matches_dense_generate(self, arch):
        cfg = get_config(arch).scaled_down(num_layers=2, d_model=64,
                                           vocab=256)
        params = tf.init(KEY, cfg, jnp.float32)
        prompts = [jax.random.randint(jax.random.PRNGKey(i + 1), (1, n),
                                      0, cfg.vocab)
                   for i, n in enumerate([7, 12])]
        new, max_len, pg = 6, 64, 8
        got = self._run_paged(cfg, params, prompts, new, max_len, pg)
        for i, pr in enumerate(prompts):
            want = np.asarray(generate(params, cfg, pr, max_new=new,
                                       max_len=max_len,
                                       dtype=jnp.float32))[0]
            assert np.array_equal(np.array(got[i]), want), (arch, i)

    @pytest.mark.parametrize("arch", ["qwen3_0p6b", "deepseek_v2_236b"])
    def test_forced_pallas_decode_step(self, arch):
        """The Pallas paged kernel (interpret) and the jnp ref produce
        the same decode step through the full model dispatch."""
        cfg = get_config(arch).scaled_down(num_layers=2, d_model=64,
                                           vocab=256)
        params = tf.init(KEY, cfg, jnp.float32)
        prompts = [jax.random.randint(jax.random.PRNGKey(9), (1, 5),
                                      0, cfg.vocab)]
        prev = layers.set_attention_impl("pallas")
        try:
            got = self._run_paged(cfg, params, prompts, 3, 32, 8)
        finally:
            layers.set_attention_impl(prev)
        want = self._run_paged(cfg, params, prompts, 3, 32, 8)
        assert got == want


class TestRaggedPrefill:
    # qwen/deepseek take the padded-final-chunk path; mamba (recurrent)
    # and mixtral (SWA rolling buffer) the exact-remainder path
    @pytest.mark.parametrize("arch", ["qwen3_0p6b", "deepseek_v2_236b",
                                      "mamba2_2p7b", "mixtral_8x22b"])
    def test_arbitrary_prompt_length(self, arch):
        cfg = get_config(arch).scaled_down()
        params = tf.init(KEY, cfg, jnp.float32)
        s = 19  # 2 full chunks of 8 + remainder 3
        tokens = jax.random.randint(KEY, (2, s), 0, cfg.vocab)
        c1 = tf.init_caches(cfg, 2, 64, jnp.float32)
        c2 = tf.init_caches(cfg, 2, 64, jnp.float32)
        t1, c1 = make_prefill_step(cfg, chunk=64)(params, tokens, c1)
        t2, c2 = make_prefill_step(cfg, chunk=8)(params, tokens, c2)
        np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
        # len counters rewound to the true prompt length
        for key, leaf in c2["blocks"].items():
            if key == "len":
                assert (np.asarray(leaf) == s).all()
        if "k" in c2["blocks"]:
            np.testing.assert_allclose(
                np.asarray(c1["blocks"]["k"][:, :, :s]),
                np.asarray(c2["blocks"]["k"][:, :, :s]), atol=1e-5)

    def test_generate_with_ragged_prompt(self):
        """End-to-end: generate() now accepts prompts that don't divide
        the chunk (it crashed on the seed's assert)."""
        cfg = get_config("qwen3_0p6b").scaled_down(num_layers=2, d_model=64,
                                                   vocab=128)
        params = tf.init(KEY, cfg, jnp.float32)
        prompt = jax.random.randint(KEY, (2, 11), 0, cfg.vocab)
        out = generate(params, cfg, prompt, max_new=4, max_len=32,
                       dtype=jnp.float32)
        assert out.shape == (2, 4)


class TestEngine:
    def _cfg_params(self):
        cfg = get_config("qwen3_0p6b").scaled_down(num_layers=2, d_model=64,
                                                   vocab=256)
        return cfg, tf.init(KEY, cfg, jnp.float32)

    def test_trace_fifo_no_leaks_matches_dense(self):
        cfg, params = self._cfg_params()
        rng = np.random.default_rng(0)
        reqs = [(rng.integers(0, cfg.vocab, (n,)).astype(np.int32), m)
                for n, m in [(7, 5), (19, 3), (12, 8), (5, 2), (30, 6),
                             (9, 1)]]
        eng = ServingEngine(params, cfg, max_slots=2, max_len=128,
                            page_size=8, prefill_chunk=8)
        free0 = eng.allocator.num_free
        for p, m in reqs:
            eng.submit(p, m)
        done = eng.run()
        # no page leaks, block tables fully unmapped
        assert eng.allocator.num_free == free0
        assert (eng.block_tables == -1).all()
        # FIFO: requests START (first token) in submission order
        starts = sorted(done, key=lambda r: r.t_first)
        assert [r.rid for r in starts] == list(range(len(reqs)))
        # every request reproduces its dense greedy run exactly
        for r in done:
            p, m = reqs[r.rid]
            want = np.asarray(generate(params, cfg, jnp.asarray(p)[None],
                                       max_new=m, max_len=128,
                                       dtype=jnp.float32))[0]
            assert np.array_equal(np.array(r.tokens), want), r.rid
        stats = latency_stats(done)
        assert stats["tokens"] == sum(m for _, m in reqs)
        assert stats["token_p50_s"] <= stats["token_p99_s"]

    def test_admission_blocks_on_scarce_pages(self):
        """With a pool sized for ~one request, the second queues until
        the first retires — and still completes correctly."""
        cfg, params = self._cfg_params()
        rng = np.random.default_rng(1)
        p1 = rng.integers(0, cfg.vocab, (10,)).astype(np.int32)
        p2 = rng.integers(0, cfg.vocab, (10,)).astype(np.int32)
        # pages_for(10 + 6, 8) = 2 pages per request; pool of 3 forces
        # serialization despite 2 free slots
        eng = ServingEngine(params, cfg, max_slots=2, max_len=64,
                            page_size=8, num_pages=3, prefill_chunk=8)
        eng.submit(p1, 6)
        eng.submit(p2, 6)
        eng.step()
        assert eng.active == 1 and eng.pending == 1  # second is queued
        done = eng.run()
        assert len(done) == 2
        assert eng.allocator.num_free == 3
        for r, p in zip(sorted(done, key=lambda r: r.rid), (p1, p2)):
            want = np.asarray(generate(params, cfg, jnp.asarray(p)[None],
                                       max_new=6, max_len=64,
                                       dtype=jnp.float32))[0]
            assert np.array_equal(np.array(r.tokens), want)

    def test_oversized_request_rejected(self):
        cfg, params = self._cfg_params()
        eng = ServingEngine(params, cfg, max_slots=1, max_len=32,
                            page_size=8, prefill_chunk=8)
        with pytest.raises(ValueError):
            eng.submit(np.zeros((30,), np.int32), 8)
        # undersubscribed POOL: a request that fits max_len but can
        # never fit the pool must be rejected, not queued forever
        eng = ServingEngine(params, cfg, max_slots=1, max_len=64,
                            page_size=8, num_pages=2, prefill_chunk=8)
        with pytest.raises(ValueError):
            eng.submit(np.zeros((20,), np.int32), 8)  # needs 4 of 2 pages

    def test_malformed_request_rejected_before_mutation(self):
        """Empty / non-1-D prompts and max_new < 1 are caller bugs: clear
        ValueError, and NO counter or queue mutation (a half-admitted
        request would wedge the FIFO)."""
        cfg, params = self._cfg_params()
        eng = ServingEngine(params, cfg, max_slots=1, max_len=32,
                            page_size=8, prefill_chunk=8)
        with pytest.raises(ValueError, match="non-empty"):
            eng.submit(np.zeros((0,), np.int32), 4)
        with pytest.raises(ValueError, match="1-D"):
            eng.submit(np.zeros((2, 3), np.int32), 4)
        with pytest.raises(ValueError, match="max_new"):
            eng.submit(np.array([5, 7], np.int32), 0)
        with pytest.raises(ValueError, match="max_new"):
            eng.submit(np.array([5, 7], np.int32), -3)
        assert eng.pending == 0
        assert eng._rejected == 0  # malformed != capacity-rejected
        assert eng._next_rid == 0
        # and the engine still works after the rejects
        req = eng.submit(np.array([5, 7], np.int32), 2)
        assert req.rid == 0 and eng.pending == 1

    def test_prompt_lengths_share_one_prefill_compile(self):
        """Sub-chunk prompts bucket to one padded shape with the real
        length traced — admission must not recompile per length."""
        cfg, params = self._cfg_params()
        eng = ServingEngine(params, cfg, max_slots=2, max_len=64,
                            page_size=8, prefill_chunk=16)
        rng = np.random.default_rng(2)
        for n in (3, 7, 11, 14):  # all bucket to the 16-token shape
            eng.submit(rng.integers(0, cfg.vocab, (n,)).astype(np.int32), 2)
        done = eng.run()
        assert len(done) == 4
        assert eng._prefill._cache_size() == 1
        for r in done:  # and the bucketing changes no tokens
            want = np.asarray(generate(
                params, cfg, jnp.asarray(r.prompt)[None], max_new=2,
                max_len=64, dtype=jnp.float32))[0]
            assert np.array_equal(np.array(r.tokens), want), r.rid

    def test_eos_at_prefill_terminates(self):
        cfg, params = self._cfg_params()
        prompt = np.array([5, 7, 11], np.int32)
        probe = ServingEngine(params, cfg, max_slots=1, max_len=64,
                              page_size=8, prefill_chunk=8)
        probe.submit(prompt, 1)
        first = probe.run()[0].tokens[0]
        eng = ServingEngine(params, cfg, max_slots=1, max_len=64,
                            page_size=8, prefill_chunk=8, eos_id=first)
        eng.submit(prompt, 8)
        done = eng.run()
        assert done[0].tokens == [first]  # stopped at the prefill token
        assert eng.allocator.num_free == eng.num_pages

    def test_unsupported_family_raises(self):
        cfg = get_config("mamba2_2p7b").scaled_down()
        with pytest.raises(NotImplementedError):
            ServingEngine({}, cfg)
        with pytest.raises(NotImplementedError):
            tf.init_caches(cfg, 2, 64, jnp.float32, cache_layout="paged")
