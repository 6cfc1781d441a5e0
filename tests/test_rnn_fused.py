"""Fused-backward GRU/LSTM sequence ops vs scan_rnn autodiff — values and
gradients, covering masks, reverse (flip routing in gru_layer/lstm_layer),
non-zero boot state, the LSTM's reverse Pallas kernel (interpret mode) with
the bias and peephole gradients it accumulates in its time loop, and its
forward kernel with the input projection it makes itself."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu.nn as nn
import paddle_tpu.ops as O
from paddle_tpu.analysis.jaxpr_walk import walk_eqns
from paddle_tpu.ops.rnn_fused import gru_sequence_fused, lstm_sequence_fused
from paddle_tpu.utils.flags import FLAGS


def _mask(lens, T):
    return jnp.asarray((np.arange(T)[None]
                        < np.asarray(lens)[:, None]).astype(np.float32))


class TestGruFused:
    def _ref(self, xp, mask, wh, h0):
        def step(h, xp_t):
            return (lambda h2: (h2, h2))(O.gru_step(xp_t, h, wh))
        return O.scan_rnn(step, h0, xp, mask)

    @pytest.mark.parametrize("lens", [(5, 3, 1), (5, 5, 5)])
    def test_forward_and_grads(self, lens):
        rs = np.random.RandomState(0)
        B, T, H = 3, 5, 4
        xp = jnp.asarray(rs.randn(B, T, 3 * H).astype(np.float32))
        mask = _mask(lens, T)
        wh = jnp.asarray(0.4 * rs.randn(H, 3 * H).astype(np.float32))
        h0 = jnp.asarray(rs.randn(B, H).astype(np.float32))
        ct_seq = jnp.asarray(rs.randn(B, T, H).astype(np.float32))
        ct_fin = jnp.asarray(rs.randn(B, H).astype(np.float32))

        ref_fin, ref_seq = self._ref(xp, mask, wh, h0)
        new_seq, new_fin = gru_sequence_fused(xp, mask, wh, h0, False)
        np.testing.assert_allclose(np.asarray(ref_seq), np.asarray(new_seq),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ref_fin), np.asarray(new_fin),
                                   rtol=1e-5, atol=1e-6)

        def loss_ref(xp, wh, h0):
            fin, seq = self._ref(xp, mask, wh, h0)
            return jnp.sum(seq * ct_seq) + jnp.sum(fin * ct_fin)

        def loss_new(xp, wh, h0):
            seq, fin = gru_sequence_fused(xp, mask, wh, h0, False)
            return jnp.sum(seq * ct_seq) + jnp.sum(fin * ct_fin)

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(xp, wh, h0)
        g_new = jax.grad(loss_new, argnums=(0, 1, 2))(xp, wh, h0)
        for name, a, b in zip(("xp", "wh", "h0"), g_ref, g_new):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5, err_msg=name)

    @pytest.mark.parametrize("layer,lens", [
        ("gru_reverse", (6, 4, 2)), ("bigru", (6, 4, 2)),
        ("bigru", (6, 3, 5, 1))],
        ids=["gru_reverse", "bigru_ragged3", "bigru_ragged4"])
    def test_layers_match_scan_reference(self, layer, lens):
        """gru_layer's flip-routed reverse == scan_rnn(reverse=True), and
        bigru_layer == a forward and a reversed scan over the same input,
        over ragged masks: values, final state and every gradient."""
        rs = np.random.RandomState(1)
        B, T, D, H = len(lens), 6, 5, 4
        x = jnp.asarray(rs.randn(B, T, D).astype(np.float32))
        mask = _mask(lens, T)
        r = lambda *shape, scale=0.4: jnp.asarray(  # noqa: E731
            scale * rs.randn(*shape).astype(np.float32))
        # (wx, wh, b) of the reversed direction, then of the forward one
        bw = (r(D, 3 * H), r(H, 3 * H), r(3 * H, scale=0.1))
        fw = (r(D, 3 * H), r(H, 3 * H), r(3 * H, scale=0.1))
        ct = r(B, T, H, scale=1.0)

        def scan(x, wx, wh, b, reverse):
            def step(h, xp_t):
                h2 = O.gru_step(xp_t, h, wh)
                return h2, h2
            fin, seq = O.scan_rnn(step, jnp.zeros((B, H)), O.linear(x, wx, b),
                                  mask, reverse=reverse)
            return seq, fin

        if layer == "gru_reverse":
            def new(x, bw, fw):
                return O.gru_layer(x, mask, *bw, reverse=True)

            def ref(x, bw, fw):
                return scan(x, *bw, True)
        else:
            def new(x, bw, fw):
                return O.bigru_layer(x, mask, *fw, *bw)

            def ref(x, bw, fw):
                return (scan(x, *fw, False)[0],) + scan(x, *bw, True)

        got, want = new(x, bw, fw), ref(x, bw, fw)
        assert len(got) == len(want)      # (h_fw,) h_bw, h_bw_final
        for a, b2 in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       rtol=1e-5, atol=1e-6)

        def loss(fn):
            def f(x, bw, fw):
                *seqs, fin = fn(x, bw, fw)
                return (sum(jnp.sum(s * ct * (i + 1.0))
                            for i, s in enumerate(seqs)) + jnp.sum(fin ** 2))
            return f

        ga = jax.grad(loss(new), argnums=(0, 1, 2))(x, bw, fw)
        gb = jax.grad(loss(ref), argnums=(0, 1, 2))(x, bw, fw)
        leaves = jax.tree_util.tree_leaves
        for a, b2 in zip(leaves(ga), leaves(gb)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       rtol=1e-4, atol=1e-5)


class TestLstmFused:
    def _ref(self, xp, mask, wh, h0, c0):
        def step(carry, xp_t):
            h, c = carry
            h2, c2 = O.lstm_step(xp_t, h, c, wh)
            return (h2, c2), h2
        return O.scan_rnn(step, (h0, c0), xp, mask)

    @pytest.mark.parametrize("lens", [(5, 3, 1), (5, 5, 5)])
    def test_forward_and_grads(self, lens):
        rs = np.random.RandomState(2)
        B, T, H = 3, 5, 4
        xp = jnp.asarray(rs.randn(B, T, 4 * H).astype(np.float32))
        mask = _mask(lens, T)
        wh = jnp.asarray(0.4 * rs.randn(H, 4 * H).astype(np.float32))
        h0 = jnp.asarray(rs.randn(B, H).astype(np.float32))
        c0 = jnp.asarray(rs.randn(B, H).astype(np.float32))
        ct_seq = jnp.asarray(rs.randn(B, T, H).astype(np.float32))

        (rf, rc), rseq = self._ref(xp, mask, wh, h0, c0)
        zp = jnp.zeros((H,), jnp.float32)
        zb = jnp.zeros((4 * H,), jnp.float32)
        nseq, nf, nc = lstm_sequence_fused(xp, zb, mask, wh, h0, c0,
                                           zp, zp, zp, False)
        np.testing.assert_allclose(np.asarray(rseq), np.asarray(nseq),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(rf), np.asarray(nf),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(rc), np.asarray(nc),
                                   rtol=1e-5, atol=1e-6)

        def loss_ref(xp, wh, h0, c0):
            (f, c), seq = self._ref(xp, mask, wh, h0, c0)
            return jnp.sum(seq * ct_seq) + jnp.sum(f) + jnp.sum(c)

        def loss_new(xp, wh, h0, c0):
            seq, f, c = lstm_sequence_fused(xp, zb, mask, wh, h0, c0,
                                            zp, zp, zp, False)
            return jnp.sum(seq * ct_seq) + jnp.sum(f) + jnp.sum(c)

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(xp, wh, h0, c0)
        g_new = jax.grad(loss_new, argnums=(0, 1, 2, 3))(xp, wh, h0, c0)
        for name, a, b in zip(("xp", "wh", "h0", "c0"), g_ref, g_new):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5, err_msg=name)


class TestLstmFusedPeepholes:
    def test_peephole_grads_match_scan_reference(self):
        """Peephole cell through the fused VJP == scan_rnn(lstm_step) with
        the same check weights — values and every gradient incl. d_peep."""
        rs = np.random.RandomState(5)
        B, T, H = 3, 5, 4
        xp = jnp.asarray(rs.randn(B, T, 4 * H).astype(np.float32))
        mask = _mask((5, 3, 1), T)
        wh = jnp.asarray(0.4 * rs.randn(H, 4 * H).astype(np.float32))
        pi = jnp.asarray(rs.randn(H).astype(np.float32) * 0.3)
        pf = jnp.asarray(rs.randn(H).astype(np.float32) * 0.3)
        po = jnp.asarray(rs.randn(H).astype(np.float32) * 0.3)
        z = jnp.zeros((B, H), jnp.float32)
        ct_seq = jnp.asarray(rs.randn(B, T, H).astype(np.float32))

        def ref(xp, wh, pi, pf, po):
            def step(carry, xp_t):
                h, c = carry
                h2, c2 = O.lstm_step(xp_t, h, c, wh, peep_i=pi, peep_f=pf,
                                     peep_o=po)
                return (h2, c2), h2
            (f, c), seq = O.scan_rnn(step, (z, z), xp, mask)
            return jnp.sum(seq * ct_seq) + jnp.sum(f) + 2.0 * jnp.sum(c)

        def new(xp, wh, pi, pf, po):
            seq, f, c = lstm_sequence_fused(xp, jnp.zeros((4 * H,)), mask,
                                            wh, z, z, pi, pf, po, False)
            return jnp.sum(seq * ct_seq) + jnp.sum(f) + 2.0 * jnp.sum(c)

        np.testing.assert_allclose(
            float(ref(xp, wh, pi, pf, po)), float(new(xp, wh, pi, pf, po)),
            rtol=1e-5)
        g_ref = jax.grad(ref, argnums=(0, 1, 2, 3, 4))(xp, wh, pi, pf, po)
        g_new = jax.grad(new, argnums=(0, 1, 2, 3, 4))(xp, wh, pi, pf, po)
        for name, a, b in zip(("xp", "wh", "pi", "pf", "po"), g_ref, g_new):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5, err_msg=name)


def _backward_kernel(monkeypatch, on, forward=False):
    """Which loops lstm_sequence_fused(..., allow_pallas=True) takes: the
    reverse Pallas kernel (interpret mode here) or the lax.scan, and
    (``forward``) the forward kernel, which makes the input projection where
    the op has its operands, or the lax.scan."""
    monkeypatch.setattr(
        "paddle_tpu.ops.rnn_fused.rnn_kernel_ok",
        lambda B, H, gates, backward=False, proj_dim=None:
        on if backward else forward)


class TestLstmKernelBackward:
    """The reverse kernel hands back d_b, d_pi, d_pf, d_po beside d_z, d_h0
    and d_c0; nothing but the three matrix products reads d_z after it."""

    B, T, H = 16, 6, 8            # two sublane tiles of rows
    RAGGED = (6, 3, 1, 5, 2, 6, 4, 1, 6, 1, 2, 3, 5, 4, 6, 2)

    @pytest.mark.parametrize("projection", [
        "caller", "op", "forward_kernel_xp", "forward_kernel_d128",
        "forward_kernel_dH"])
    @pytest.mark.parametrize("residuals", ["float32", "bfloat16"])
    @pytest.mark.parametrize("peepholes", [True, False],
                             ids=["peepholes", "plain"])
    @pytest.mark.parametrize("lens", [RAGGED, (6,) * 16],
                             ids=["ragged", "full"])
    def test_every_gradient_matches_scan_reference(self, monkeypatch, lens,
                                                   peepholes, residuals,
                                                   projection):
        """Every gradient of the kernel path against autodiff of
        scan_rnn(lstm_step), over masked tails (rows of length 1, full
        rows), with and without peepholes, with float32 and with bf16
        residual streams (the production policy's), with the projection
        made by the caller (the op's first gradient is d_xp) and by the op
        (dx and d_w_x).  bf16 residuals round what the backward recomputes
        its gates from, so there the tight comparison is with the lax.scan
        backward over the same residuals.  Under the bf16 policy an op that
        owns the projection stores d_z in bf16 on the kernel path, which
        reaches the three products that read it (dx, d_w_x, d_w_h) and
        nothing else; a caller's d_xp is never rounded.  The
        ``forward_kernel`` cases run the forward kernel too, handed the
        caller's projection (``_xp``) or ``x`` [B,T,D], ``w_x`` and ``b``, from
        which it makes the projection itself, for D=128 and D=H; there the
        primal-only call (no residuals) is compared as well."""
        rs = np.random.RandomState(7)
        B, T, H = self.B, self.T, self.H
        D = {"forward_kernel_d128": 128, "forward_kernel_dH": H}.get(
            projection, 5)
        monkeypatch.setattr(FLAGS, "compute_dtype", residuals)
        r = lambda *shape, scale=1.0: jnp.asarray(  # noqa: E731
            scale * rs.randn(*shape).astype(np.float32))
        forward = projection.startswith("forward_kernel")
        owned = projection not in ("caller", "forward_kernel_xp")
        x = r(B, T, D) if owned else r(B, T, 4 * H)
        wx = r(D, 4 * H, scale=0.4) if owned else None
        b, wh = r(4 * H, scale=0.3), r(H, 4 * H, scale=0.4)
        h0, c0 = r(B, H), r(B, H)
        if forward:             # the forward kernel boots from zeros
            h0, c0 = jnp.zeros_like(h0), jnp.zeros_like(c0)
        peeps = tuple(r(H, scale=0.3 * peepholes) for _ in range(3))
        ct_seq, ct_h, ct_c = r(B, T, H), r(B, H), r(B, H)
        mask = _mask(lens, T)

        def objective(seq, f, c):
            return (jnp.sum(seq * ct_seq) + jnp.sum(f * ct_h)
                    + jnp.sum(c * ct_c))

        def ref(x, b, wh, h0, c0, pi, pf, po, wx):
            def step(carry, xp_t):
                h, c = carry
                h2, c2 = O.lstm_step(xp_t, h, c, wh, peep_i=pi, peep_f=pf,
                                     peep_o=po)
                return (h2, c2), h2
            xp = O.linear(x, wx) if owned else x
            (f, c), seq = O.scan_rnn(step, (h0, c0), xp + b, mask)
            return objective(seq, f, c)

        def new(x, b, wh, h0, c0, pi, pf, po, wx):
            return objective(*lstm_sequence_fused(
                x, b, mask, wh, h0, c0, pi, pf, po, True, peepholes, wx))

        args = (x, b, wh, h0, c0) + peeps + (wx,)
        names = ("x", "b", "wh", "h0", "c0", "pi", "pf", "po", "wx")
        every = tuple(range(9 if owned else 8))
        argnums = every if peepholes else (0, 1, 2, 3, 4) + every[8:]
        g_ref = dict(zip(argnums, jax.grad(ref, argnums)(*args)))
        _backward_kernel(monkeypatch, False, forward)
        g_scan = dict(zip(argnums, jax.grad(new, argnums)(*args)))
        _backward_kernel(monkeypatch, True, forward)
        g_new = jax.grad(new, every)(*args)
        tol = 1e-4 if residuals == "float32" else 3e-2
        if forward:     # outputs and finals of the residual-free kernel
            np.testing.assert_allclose(float(new(*args)), float(ref(*args)),
                                       rtol=tol)
        for n in argnums:
            a, s, k = g_ref[n], g_scan[n], g_new[n]
            scale = float(jnp.max(jnp.abs(a)))
            np.testing.assert_allclose(np.asarray(k), np.asarray(a),
                                       rtol=tol, atol=tol * scale,
                                       err_msg=names[n])
            reads_narrow_d_z = owned and names[n] in ("x", "wx", "wh")
            tight = tol if reads_narrow_d_z else 1e-5
            np.testing.assert_allclose(np.asarray(k), np.asarray(s),
                                       rtol=tight, atol=tight * scale,
                                       err_msg=names[n] + " (scan backward)")
        if not peepholes:       # no accumulator: the gradients are zeros
            assert all(not np.asarray(g).any() for g in g_new[5:8])

    @pytest.mark.parametrize("peepholes", [True, False],
                             ids=["peepholes", "plain"])
    def test_only_the_store_of_d_z_rounds(self, peepholes):
        """Asked for a bf16 d_z, the reverse kernel returns the float32
        variant's d_z rounded to nearest even, bit for bit; what the kernel
        computes from d_z itself (the carry product behind d_h0 and d_c0,
        the bias and peephole accumulators) reads the float32 value, so
        those results are the float32 variant's bit for bit."""
        from paddle_tpu.ops.pallas_kernels import _lstm_bwd_pallas_raw

        rs = np.random.RandomState(3)
        B, T, H = self.B, self.T, self.H
        r = lambda *shape, scale=1.0, dt=jnp.float32: jnp.asarray(  # noqa: E731
            scale * rs.randn(*shape).astype(np.float32)).astype(dt)
        bf16 = jnp.bfloat16
        args = (r(T, B, H), jnp.moveaxis(_mask(self.RAGGED, T), 1, 0),
                r(T, B, 4 * H, dt=bf16), r(T, B, H, dt=bf16),
                r(4 * H, H, scale=0.4),
                *(r(1, H, scale=0.3 * peepholes) for _ in range(3)),
                r(B, H), r(B, H))
        wide = _lstm_bwd_pallas_raw(*args, has_peepholes=peepholes)
        narrow = _lstm_bwd_pallas_raw(*args, has_peepholes=peepholes,
                                      dz_dtype=bf16)
        assert wide[0].dtype == jnp.float32 and narrow[0].dtype == bf16
        assert np.asarray(wide[0]).any()
        np.testing.assert_array_equal(
            np.asarray(narrow[0]).view(np.uint16),
            np.asarray(wide[0].astype(bf16)).view(np.uint16))
        assert (narrow[4] is None) == (not peepholes)
        for name, w, n in zip(("d_h0", "d_c0", "d_b", "d_peep"),
                              wide[1:], narrow[1:]):
            if w is not None:
                assert n.dtype == jnp.float32
                np.testing.assert_array_equal(np.asarray(n), np.asarray(w),
                                              err_msg=name)

    @pytest.mark.parametrize("options", [
        dict(reverse=True), dict(use_peepholes=False), dict(bias_attr=False),
        dict(projected_input=True)],
        ids=["reverse", "no_peepholes", "no_bias", "projected_input"])
    @pytest.mark.parametrize("forward", [False, True],
                             ids=["scan_forward", "kernel_forward"])
    def test_lstmemory_options_on_the_kernel_path(self, monkeypatch,
                                                  options, forward):
        """nn.lstmemory's variants reach the kernel path with the right
        operands: the flip for ``reverse``, no peephole accumulators, a
        bias that is no parameter, an input that is the projection (which
        the forward kernel then takes as it is, where the others hand it
        ``x``, ``w_x`` and the bias)."""
        rs = np.random.RandomState(11)
        B, T, H = self.B, self.T, self.H
        D = 4 * H if options.get("projected_input") else 5
        reverse = options.get("reverse", False)
        nn.reset_naming()
        layer = nn.lstmemory(nn.data("x", size=D, is_seq=True), H, name="l",
                             **options)
        topo = nn.Topology([layer])
        params, state = topo.init(jax.random.PRNGKey(0))
        params = {k: jnp.asarray(0.3 * rs.randn(*v.shape).astype(np.float32))
                  for k, v in params.items()}
        xs = jnp.asarray(rs.randn(B, T, D).astype(np.float32))
        lengths = np.asarray(self.RAGGED, np.int32)
        ct = jnp.asarray(rs.randn(B, T, H).astype(np.float32))

        def new(params, xs):
            out = topo.apply(params, state, {"x": (xs, lengths)})[0]["l"]
            return (jnp.sum(out.value * ct) + jnp.sum(out.state["final_h"])
                    + 2.0 * jnp.sum(out.state["final_c"]))

        def ref(params, xs):
            xp = xs if "_l.wx" not in params else O.linear(xs, params["_l.wx"])
            if "_l.wbias" in params:
                xp = xp + params["_l.wbias"]
            pk = {f"peep_{g}": params[f"_l.check_{g}"] for g in "ifo"
                  if f"_l.check_{g}" in params}

            def step(carry, xp_t):
                h2, c2 = O.lstm_step(xp_t, *carry, params["_l.w0"], **pk)
                return (h2, c2), h2
            zeros = jnp.zeros((B, H), jnp.float32)
            (f, c), seq = O.scan_rnn(step, (zeros, zeros), xp,
                                     _mask(lengths, T), reverse=reverse)
            return jnp.sum(seq * ct) + jnp.sum(f) + 2.0 * jnp.sum(c)

        _backward_kernel(monkeypatch, True, forward)
        np.testing.assert_allclose(float(new(params, xs)),
                                   float(ref(params, xs)), rtol=1e-5)
        g_new = jax.grad(new, (0, 1))(params, xs)
        g_ref = jax.grad(ref, (0, 1))(params, xs)
        assert set(g_new[0]) == set(g_ref[0])
        for name in g_ref[0]:
            np.testing.assert_allclose(
                np.asarray(g_new[0][name]), np.asarray(g_ref[0][name]),
                rtol=1e-4, atol=1e-5, err_msg=name)
        np.testing.assert_allclose(np.asarray(g_new[1]), np.asarray(g_ref[1]),
                                   rtol=1e-4, atol=1e-5, err_msg="x")

    @pytest.mark.parametrize("xp_dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("D", [128, 8], ids=["d128", "dH"])
    def test_forward_kernel_rounds_the_projection_as_linear_does(self, D,
                                                                 xp_dtype):
        """The projection the forward kernel makes, read off its first
        pre-activations (the carry is zero there): linear(x, w_x) + b with
        the operands in the compute dtype and the result in ``xp_dtype``,
        float32 by default and bf16 under --amp, where the product and then
        the sum are each rounded to it."""
        from paddle_tpu.ops.pallas_kernels import _lstm_pallas_raw

        rs = np.random.RandomState(13)
        B, T, H = self.B, 2, self.H
        r = lambda *shape, scale=1.0: jnp.asarray(  # noqa: E731
            scale * rs.randn(*shape).astype(np.float32))
        x, wx, b, wh = r(T, B, D), r(D, 4 * H, scale=0.4), r(4 * H), r(H, 4 * H)
        dt = jnp.dtype(xp_dtype)
        z = _lstm_pallas_raw(x, jnp.ones((T, B)), wh, *(jnp.zeros(H),) * 3,
                             w_x=wx, b=b, xp_dtype=dt)[3]
        want = jnp.dot(x[0], wx, preferred_element_type=dt) + b.astype(dt)
        assert want.dtype == dt and z.dtype == jnp.float32
        ulp = 2.0 ** -8 if xp_dtype == "bfloat16" else 2.0 ** -22
        np.testing.assert_allclose(np.asarray(z[0]),
                                   np.asarray(want.astype(jnp.float32)),
                                   rtol=ulp, atol=ulp)
        if xp_dtype == "bfloat16":      # z is a bf16 value, widened
            np.testing.assert_array_equal(
                np.asarray(z[0]), np.asarray(z[0].astype(dt).astype(z.dtype)))

    def test_only_the_three_products_read_d_z(self, monkeypatch):
        """Structure of the kernel path's backward: XLA reads a [T,B,4H] or
        [T,B,H] array for nothing but dW_x, dx and dW_h: no reduce_sum over
        one (the bias), no product against one (the peepholes).  The scan
        path, which keeps those four reductions, shows that they are seen."""
        B, T, H, D = self.B, self.T, self.H, 5
        f32 = jnp.float32
        mask = _mask(self.RAGGED, T)

        args = [jnp.zeros(s, f32) for s in [
            (B, T, D), (D, 4 * H), (H, 4 * H), (4 * H,), (H,), (H,), (H,)]]
        cts = (jnp.zeros((B, T, H), f32),
               (jnp.zeros((B, H), f32), jnp.zeros((B, H), f32)))

        def passes(kernel):
            _backward_kernel(monkeypatch, kernel)

            # a function of its own for each trace: JAX keys its caches on it
            def layer(x, w_x, w_h, b, pi, pf, po):
                return O.lstm_layer(x, mask, w_x, w_h, b, peep_i=pi,
                                    peep_f=pf, peep_o=po)

            def backward(args, cotangents):  # no loss: it would reduce too
                return jax.vjp(layer, *args)[1](cotangents)

            jaxpr = jax.make_jaxpr(backward)(args, cts)
            seen = {"dot_general": 0, "reduce_sum": 0}
            for eqn, path in walk_eqns(jaxpr):
                if "pallas_call" in path:     # XLA does not see inside
                    continue
                big = [v.aval.shape for v in eqn.invars
                       if getattr(v.aval, "ndim", 0) == 3
                       and v.aval.shape[-1] in (H, 4 * H)
                       and set(v.aval.shape[:2]) == {B, T}]
                if big and eqn.primitive.name in seen:
                    seen[eqn.primitive.name] += 1
            return seen

        assert passes(kernel=True) == {"dot_general": 3, "reduce_sum": 0}
        assert passes(kernel=False) == {"dot_general": 6, "reduce_sum": 1}
