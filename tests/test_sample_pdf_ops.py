"""Per-element sampling (multisample) family, *_like samplers, pdf ops,
and SVMOutput gradient.

Parity targets: src/operator/random/multisample_op.{h,cc} (sample_* with
tensor-valued distribution parameters), sample_op.cc:166-262 (scalar
generalized NB + the *_like family), random/pdf_op.{h,cc} (random_pdf_*
with is_log), svm_output.cc (L1_SVM/L2_SVM backward kernels).
"""
import numpy as np
import pytest
from scipy import stats

import mxnet_tpu as mx
from mxnet_tpu import nd


@pytest.fixture(autouse=True)
def _seed():
    mx.random.seed(1234)


N = 20000


class TestMultisample:
    """sample_*: each parameter element owns a block of samples; output
    shape = params.shape + attrs['shape'] (multisample_op.h
    MultiSampleOpShape)."""

    def test_sample_uniform(self):
        low = nd.array(np.array([0.0, 10.0], np.float32))
        high = nd.array(np.array([1.0, 20.0], np.float32))
        s = nd.sample_uniform(low, high, shape=(N,)).asnumpy()
        assert s.shape == (2, N)
        assert 0.0 <= s[0].min() and s[0].max() <= 1.0
        assert 10.0 <= s[1].min() and s[1].max() <= 20.0
        np.testing.assert_allclose(s.mean(axis=1), [0.5, 15.0], atol=0.1)

    def test_sample_normal(self):
        mu = nd.array(np.array([-3.0, 5.0], np.float32))
        sigma = nd.array(np.array([1.0, 4.0], np.float32))
        s = nd.sample_normal(mu, sigma, shape=(N,)).asnumpy()
        np.testing.assert_allclose(s.mean(axis=1), [-3.0, 5.0], atol=0.15)
        np.testing.assert_allclose(s.std(axis=1), [1.0, 4.0], rtol=0.05)

    def test_sample_gamma(self):
        alpha = nd.array(np.array([2.0, 9.0], np.float32))
        beta = nd.array(np.array([1.0, 0.5], np.float32))  # scale
        s = nd.sample_gamma(alpha, beta, shape=(N,)).asnumpy()
        np.testing.assert_allclose(s.mean(axis=1), [2.0, 4.5], rtol=0.05)

    def test_sample_exponential(self):
        lam = nd.array(np.array([0.5, 4.0], np.float32))
        s = nd.sample_exponential(lam, shape=(N,)).asnumpy()
        np.testing.assert_allclose(s.mean(axis=1), [2.0, 0.25], rtol=0.06)

    def test_sample_poisson(self):
        lam = nd.array(np.array([1.0, 8.0], np.float32))
        s = nd.sample_poisson(lam, shape=(N,)).asnumpy()
        np.testing.assert_allclose(s.mean(axis=1), [1.0, 8.0], rtol=0.05)
        assert (s >= 0).all() and np.allclose(s, np.round(s))

    def test_sample_negative_binomial(self):
        k = nd.array(np.array([2.0, 5.0], np.float32))
        p = nd.array(np.array([0.5, 0.25], np.float32))
        s = nd.sample_negative_binomial(k, p, shape=(N,)).asnumpy()
        want = [2 * 0.5 / 0.5, 5 * 0.75 / 0.25]  # k(1-p)/p
        np.testing.assert_allclose(s.mean(axis=1), want, rtol=0.08)

    def test_sample_generalized_negative_binomial(self):
        mu = nd.array(np.array([2.0, 6.0], np.float32))
        alpha = nd.array(np.array([0.5, 0.2], np.float32))
        s = nd.sample_generalized_negative_binomial(
            mu, alpha, shape=(N,)).asnumpy()
        np.testing.assert_allclose(s.mean(axis=1), [2.0, 6.0], rtol=0.08)
        # var = mu + alpha mu^2
        want_var = [2 + 0.5 * 4, 6 + 0.2 * 36]
        np.testing.assert_allclose(s.var(axis=1), want_var, rtol=0.15)

    def test_2d_params_and_multidim_shape(self):
        mu = nd.array(np.zeros((2, 3), np.float32))
        sg = nd.array(np.ones((2, 3), np.float32))
        s = nd.sample_normal(mu, sg, shape=(5, 7))
        assert s.shape == (2, 3, 5, 7)

    def test_scalar_generalized_negative_binomial(self):
        s = nd._random_generalized_negative_binomial(
            mu=3.0, alpha=0.4, shape=(N,)).asnumpy()
        np.testing.assert_allclose(s.mean(), 3.0, rtol=0.08)
        np.testing.assert_allclose(s.var(), 3 + 0.4 * 9, rtol=0.15)


class TestLikeFamily:
    """*_like: sample with the shape/dtype of the input array
    (sample_op.cc:197-262)."""

    @pytest.mark.parametrize("op,attrs,mean", [
        ("_random_uniform_like", {"low": 2.0, "high": 4.0}, 3.0),
        ("_random_normal_like", {"loc": -1.0, "scale": 2.0}, -1.0),
        ("_random_gamma_like", {"alpha": 4.0, "beta": 0.5}, 2.0),
        ("_random_exponential_like", {"lam": 2.0}, 0.5),
        ("_random_poisson_like", {"lam": 3.0}, 3.0),
        ("_random_negative_binomial_like", {"k": 3.0, "p": 0.5}, 3.0),
        ("_random_generalized_negative_binomial_like",
         {"mu": 2.5, "alpha": 0.3}, 2.5),
    ])
    def test_like(self, op, attrs, mean):
        data = nd.zeros((100, 200))
        out = getattr(nd, op)(data, **attrs)
        assert out.shape == data.shape and out.dtype == data.dtype
        np.testing.assert_allclose(out.asnumpy().mean(), mean, atol=0.12)


class TestPdfOps:
    """random_pdf_* against scipy, incl. is_log (pdf_op.h formulas;
    gamma's beta is a RATE, negative_binomial's p is the failure prob)."""

    def test_pdf_gamma_vs_scipy(self):
        x = np.abs(np.random.RandomState(0).randn(2, 7)).astype(np.float32) + 0.1
        a = np.array([2.0, 3.0], np.float32)
        b = np.array([1.5, 0.5], np.float32)
        out = nd.random_pdf_gamma(nd.array(x), nd.array(a), nd.array(b))
        ref = stats.gamma.pdf(x, a[:, None], scale=1 / b[:, None])
        np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-4)
        lout = nd.random_pdf_gamma(nd.array(x), nd.array(a), nd.array(b),
                                   is_log=True)
        np.testing.assert_allclose(lout.asnumpy(), np.log(ref), rtol=1e-4,
                                   atol=1e-5)

    def test_pdf_normal_uniform_exponential(self):
        x = np.random.RandomState(1).randn(3, 5).astype(np.float32)
        mu = np.array([0.0, 1.0, -1.0], np.float32)
        sg = np.array([1.0, 2.0, 0.5], np.float32)
        out = nd.random_pdf_normal(nd.array(x), nd.array(mu), nd.array(sg))
        np.testing.assert_allclose(
            out.asnumpy(), stats.norm.pdf(x, mu[:, None], sg[:, None]),
            rtol=1e-4)
        xu = np.random.RandomState(2).rand(2, 4).astype(np.float32)
        lo = np.zeros(2, np.float32)
        hi = np.array([2.0, 4.0], np.float32)
        out = nd.random_pdf_uniform(nd.array(xu), nd.array(lo), nd.array(hi))
        np.testing.assert_allclose(out.asnumpy(),
                                   np.broadcast_to(1 / hi[:, None], xu.shape),
                                   rtol=1e-5)
        xe = np.abs(np.random.RandomState(3).randn(2, 4)).astype(np.float32)
        lam = np.array([0.5, 3.0], np.float32)
        out = nd.random_pdf_exponential(nd.array(xe), nd.array(lam))
        np.testing.assert_allclose(
            out.asnumpy(), stats.expon.pdf(xe, scale=1 / lam[:, None]),
            rtol=1e-4)

    def test_pdf_discrete_vs_scipy(self):
        xs = np.arange(8, dtype=np.float32)[None]
        lam = np.array([3.0], np.float32)
        out = nd.random_pdf_poisson(nd.array(xs), nd.array(lam))
        np.testing.assert_allclose(out.asnumpy(),
                                   stats.poisson.pmf(xs, lam[:, None]),
                                   rtol=1e-4)
        k = np.array([4.0], np.float32)
        p = np.array([0.3], np.float32)
        out = nd.random_pdf_negative_binomial(nd.array(xs), nd.array(k),
                                              nd.array(p))
        np.testing.assert_allclose(out.asnumpy(),
                                   stats.nbinom.pmf(xs, k[:, None], p[:, None]),
                                   rtol=1e-4)
        # generalized NB: reparam limit=1/alpha, prob=1/(mu*alpha+1)
        mu = np.array([2.0], np.float32)
        al = np.array([0.5], np.float32)
        out = nd.random_pdf_generalized_negative_binomial(
            nd.array(xs), nd.array(mu), nd.array(al))
        ref = stats.nbinom.pmf(xs, (1 / al)[:, None],
                               (1 / (mu * al + 1))[:, None])
        np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-4)

    def test_pdf_dirichlet_vs_scipy(self):
        al = np.array([[1.0, 2.0, 3.0]], np.float32)
        sm = np.random.RandomState(1).dirichlet(al[0], size=4).astype(
            np.float32)[None]
        out = nd.random_pdf_dirichlet(nd.array(sm), nd.array(al))
        ref = np.array([[stats.dirichlet.pdf(r, al[0]) for r in sm[0]]])
        np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-3)

    def test_pdf_gradient_flows(self):
        """log-pdf gradients via autodiff match the closed form
        d/dmu log N(x|mu,s) = (x-mu)/s^2 (pdf_op.h PDF_Normal_Grad)."""
        x = np.random.RandomState(5).randn(2, 3).astype(np.float32)
        mu = nd.array(np.array([0.5, -0.5], np.float32))
        sg = nd.array(np.array([1.0, 2.0], np.float32))
        mu.attach_grad()
        with mx.autograd.record():
            out = nd.random_pdf_normal(nd.array(x), mu, sg, is_log=True)
            out.sum().backward()
        want = ((x - np.array([0.5, -0.5])[:, None])
                / np.array([1.0, 2.0])[:, None] ** 2).sum(axis=1)
        np.testing.assert_allclose(mu.grad.asnumpy(), want, rtol=1e-4)


class TestSVMOutput:
    """Backward pinned against the svm_output.cc L1_SVM/L2_SVM kernels."""

    def _expected(self, x, y, margin, reg, linear):
        exp = np.zeros_like(x)
        for r in range(x.shape[0]):
            k = int(y[r])
            for c in range(x.shape[1]):
                v = x[r, c]
                if linear:
                    if c == k:
                        exp[r, c] = -float(margin > v) * reg
                    else:
                        exp[r, c] = float(margin > -v) * reg
                else:
                    if c == k:
                        exp[r, c] = (-2 * reg * (margin - v)
                                     if margin > v else 0.0)
                    else:
                        exp[r, c] = (2 * reg * (margin + v)
                                     if margin > -v else 0.0)
        return exp

    @pytest.mark.parametrize("linear", [False, True])
    def test_svm_grad(self, linear):
        x = np.array([[0.5, -0.3, 0.2], [2.0, -2.0, 0.1]], np.float32)
        y = np.array([0, 2], np.float32)
        a = nd.array(x)
        a.attach_grad()
        with mx.autograd.record():
            out = nd.SVMOutput(a, nd.array(y), margin=0.8,
                               regularization_coefficient=0.7,
                               use_linear=linear)
            out.sum().backward()
        np.testing.assert_allclose(out.asnumpy(), x)
        np.testing.assert_allclose(
            a.grad.asnumpy(), self._expected(x, y, 0.8, 0.7, linear),
            rtol=1e-5, atol=1e-6)


class TestAmpListsAreReal:
    """Every op named in amp/lists.py must exist in the registry (the r03
    verdict found SVMOutput listed while unregistered)."""

    def test_all_list_entries_registered(self):
        from mxnet_tpu.amp import lists
        from mxnet_tpu.ops import registry
        names = []
        for attr in dir(lists):
            v = getattr(lists, attr)
            if isinstance(v, (list, tuple, set)) and not attr.startswith("_"):
                names.extend(x for x in v if isinstance(x, str))
        assert names, "amp lists unexpectedly empty"
        missing = sorted({n for n in names if not registry.exists(n)})
        assert not missing, f"amp/lists.py names unregistered ops: {missing}"


class TestAggregatedOptimizer:
    """Multi-tensor updates: ``gluon.Trainer``'s one program over every
    tensor and the list form of ``Updater.__call__`` (one ``multi_sgd_*``
    dispatch, reference optimizer_op.cc:320) must both match per-param
    updates exactly."""

    def _train(self, how):
        import mxnet_tpu as mxt
        from mxnet_tpu import gluon, autograd, optimizer as opt
        mxt.random.seed(0)
        net = gluon.nn.Sequential()
        net.add(gluon.nn.Dense(16, activation="relu", in_units=8),
                gluon.nn.Dense(1, in_units=16))
        net.initialize(mxt.initializer.Xavier())
        kwargs = {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}
        params = list(net.collect_params().values())
        if how == "trainer":
            tr = gluon.Trainer(params, "sgd", kwargs)
        else:
            sgd = opt.create("sgd", param_dict=dict(enumerate(params)),
                             rescale_grad=1.0 / 32, **kwargs)
            upd = opt.get_updater(sgd)
        rs = np.random.RandomState(3)
        X = nd.array(rs.randn(32, 8).astype(np.float32))
        Y = nd.array(rs.randn(32, 1).astype(np.float32))
        L = gluon.loss.L2Loss()
        idx = list(range(len(params)))
        for _ in range(5):
            with autograd.record():
                loss = L(net(X), Y)
            loss.backward()
            if how == "trainer":
                tr.step(32)
            elif how == "list":
                upd(idx, [p.grad() for p in params],
                    [p.data() for p in params])
            else:
                for i, p in enumerate(params):
                    upd(i, p.grad(), p.data())
        return [p.data().asnumpy() for p in params]

    def test_aggregated_matches_sequential(self):
        want = self._train("loop")
        for how in ("trainer", "list"):
            for a, b in zip(self._train(how), want):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


class TestScalarRandomFamilyMoments:
    """Scalar-parameter _random_* ops: empirical moments + seed
    determinism (parity: reference test_random.py, which checks each
    sampler's mean/std against the distribution)."""

    def _draw(self, fn, **kw):
        return fn(shape=(4000,), **kw).asnumpy()

    def test_moments(self):
        import mxnet_tpu as mx
        mx.random.seed(1234)
        u = self._draw(nd.random.uniform, low=2.0, high=6.0)
        np.testing.assert_allclose(u.mean(), 4.0, atol=0.15)
        assert u.min() >= 2.0 and u.max() <= 6.0
        n = self._draw(nd.random.normal, loc=1.0, scale=3.0)
        np.testing.assert_allclose(n.mean(), 1.0, atol=0.2)
        np.testing.assert_allclose(n.std(), 3.0, rtol=0.06)
        g = self._draw(nd.random.gamma, alpha=4.0, beta=0.5)
        np.testing.assert_allclose(g.mean(), 2.0, rtol=0.08)
        e = self._draw(nd.random.exponential, scale=0.5)
        np.testing.assert_allclose(e.mean(), 0.5, rtol=0.08)
        p = self._draw(nd.random.poisson, lam=6.0)
        np.testing.assert_allclose(p.mean(), 6.0, rtol=0.05)
        np.testing.assert_allclose(p.var(), 6.0, rtol=0.15)

    def test_seed_determinism_and_divergence(self):
        import mxnet_tpu as mx
        mx.random.seed(77)
        a = nd.random.normal(shape=(64,)).asnumpy()
        b = nd.random.normal(shape=(64,)).asnumpy()
        assert not np.allclose(a, b)  # stream advances
        mx.random.seed(77)
        a2 = nd.random.normal(shape=(64,)).asnumpy()
        np.testing.assert_array_equal(a, a2)  # same seed, same stream
        mx.random.seed(78)
        a3 = nd.random.normal(shape=(64,)).asnumpy()
        assert not np.allclose(a, a3)
