"""Optimizers.

Re-design of reference python/mxnet/optimizer/optimizer.py (1875 LoC) +
src/operator/optimizer_op.cc. Each optimizer's update dispatches a fused op
(one jitted XLA computation; fusion is free on TPU where the reference needed
hand-fused CUDA kernels). Multi-precision = bf16/fp16 params with fp32 master
weights, the TPU-idiomatic recipe (reference: mp_sgd_* ops).

The ``Updater`` wrapper is what a KVStore executes server/store-side
(reference: optimizer.py:1647 get_updater).
"""
from __future__ import annotations

import math
import pickle

import numpy as np

from . import ndarray as nd
from .ndarray import ndarray as _ndmod
from .base import MXNetError
from .registry import get_register_func, get_alias_func, get_create_func

_OPT_REGISTRY = {}


class Optimizer:
    """Base optimizer (parity: optimizer.py:46)."""

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        if param_idx2name is None:
            param_idx2name = {}
        if not isinstance(param_idx2name, dict):
            raise MXNetError("param_idx2name should be a dict of param indexes to names")
        self.idx2name = param_idx2name.copy()
        self.sym_info = ()
        self.param_dict = param_dict if param_dict else {}

    # -- registry ----------------------------------------------------------
    opt_registry = _OPT_REGISTRY

    @staticmethod
    def register(klass):
        return _register(klass)

    @staticmethod
    def create_optimizer(name, **kwargs):
        return create(name, **kwargs)

    # -- state -------------------------------------------------------------
    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and weight.dtype == np.float16:
            weight_master_copy = weight.astype(np.float32)
            return (self.create_state(index, weight_master_copy),
                    weight_master_copy)
        if weight.dtype in (np.float16,) and not self.multi_precision:
            import logging
            logging.getLogger(__name__).warning(
                "Accumulating with float16 in optimizer can lead to poor "
                "accuracy or slow convergence. Consider multi_precision=True")
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        raise NotImplementedError()

    # -- fused pytree form (one-dispatch train step) -----------------------
    # Optimizers that can run their update as pure jax math over the whole
    # parameter pytree override ``fused_update``; the fused train step
    # (mxnet_tpu/fused_step.py) traces it together with forward+backward
    # into ONE donated XLA computation.  Optimizers that keep the class
    # attribute ``None`` (anything host-side/stateful: LARS norms, LAMB
    # trust ratios, sparse-lazy paths, user subclasses) silently fall back
    # to the per-param dispatch loop in Module.update.
    fused_update = None

    # True when ``fused_update`` is purely elementwise over (weight,
    # grad, state) AND accepts lr/wd as broadcastable ARRAYS, not just
    # scalars.  The mesh-fused fsdp layout (parallel/fused.py) relies on
    # both: it runs the update on flat 1-D bucket *shards* that span
    # parameter boundaries, feeding per-element lr/wd vectors — only
    # legal when no cross-element math (LARS/LAMB norms) exists.
    fused_elementwise = False

    # True when ``fused_update`` implements the float32 master copy that
    # ``multi_precision`` keeps beside a half-precision weight.
    fused_multi_precision = False

    def fused_hyperparams(self, indices):
        """Host-side per-step dynamic scalars for ``fused_update``:
        ``(lr_t, wd_t)`` python-float lists, evaluated ONCE per step
        AFTER ``_update_count`` so lr schedules/bias corrections see the
        same step count as the per-param loop.  The train step packs each
        list into one host ``float32`` array (``fused_step.
        host_hyperparams``) and passes the two as ARGUMENTS of the jitted
        step (never baked into the trace), so a changing lr schedule does
        not recompile and the call's host arguments do not grow with the
        number of tensors."""
        return ([float(self._get_lr(i)) for i in indices],
                [float(self._get_wd(i)) for i in indices])

    def fused_window_hyperparams(self, indices, steps):
        """Host-side lr/wd for a K-step scanned window (fused_step.py
        ScanTrainStep): bumps the update counts step by step — exactly
        like ``steps`` sequential ``fused_hyperparams`` calls — and
        returns ``(lrs, wds)`` as ``steps x len(indices)`` float lists.
        Schedules (and Adam's bias correction, via the subclass
        ``fused_hyperparams``) therefore advance INSIDE the window
        without ever baking a step count into the scan trace."""
        lrs, wds = [], []
        for _ in range(int(steps)):
            for i in indices:
                self._update_count(i)
            lr_t, wd_t = self.fused_hyperparams(indices)
            lrs.append(lr_t)
            wds.append(wd_t)
        return lrs, wds

    def fused_static_signature(self):
        """Hyperparameters baked into the fused trace as constants; the
        fused step retraces when this tuple changes (mutating e.g.
        ``rescale_grad`` mid-training stays correct, just slower)."""
        return (self.rescale_grad, self.clip_gradient, self.multi_precision)

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and weight.dtype == np.float16:
            original_state, weight_master_copy = state
            grad32 = grad.astype(np.float32)
            self.update(index, weight_master_copy, grad32, original_state)
            weight[:] = weight_master_copy.astype(weight.dtype)
        else:
            self.update(index, weight, grad, state)

    # -- lr/wd -------------------------------------------------------------
    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise MXNetError("LRScheduler of the optimizer has already been "
                             "defined; set lr on the scheduler")
        self.lr = lr

    @property
    def learning_rate(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = args_lr_mult.copy()

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            # parity with reference optimizer.py: weights AND norm gammas
            # keep weight decay; biases/betas/running stats are exempt
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if not isinstance(index, (list, tuple)):
            index = [index]
        for idx in index:
            if idx not in self._index_update_count:
                self._index_update_count[idx] = self.begin_num_update
            self._index_update_count[idx] += 1
            self.num_update = max(self._index_update_count[idx], self.num_update)

    def _get_lrs(self, indices):
        lr = self.learning_rate
        lrs = [lr] * len(indices)
        for i, index in enumerate(indices):
            if index in self.param_dict:
                lrs[i] *= self.param_dict[index].lr_mult
            elif index in self.lr_mult:
                lrs[i] *= self.lr_mult[index]
            elif index in self.idx2name:
                lrs[i] *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lrs

    def _get_lr(self, index):
        return self._get_lrs([index])[0]

    def _get_wds(self, indices):
        wds = [self.wd] * len(indices)
        for i, index in enumerate(indices):
            if index in self.param_dict:
                wds[i] *= self.param_dict[index].wd_mult
            elif index in self.wd_mult:
                wds[i] *= self.wd_mult[index]
            elif index in self.idx2name:
                wds[i] *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wds

    def _get_wd(self, index):
        return self._get_wds([index])[0]

    def _common_attrs(self, lr, wd):
        attrs = {"lr": lr, "wd": wd, "rescale_grad": self.rescale_grad}
        if self.clip_gradient is not None:
            attrs["clip_gradient"] = self.clip_gradient
        return attrs

    def __getstate__(self):
        return self.__dict__

    def __setstate__(self, state):
        self.__dict__.update(state)


_register = get_register_func(Optimizer, "optimizer", _OPT_REGISTRY)
register = _register
alias = get_alias_func(Optimizer, "optimizer", _OPT_REGISTRY)
create = get_create_func(Optimizer, "optimizer", _OPT_REGISTRY)


def _invoke(opname, inputs, attrs, out):
    return _ndmod.invoke(opname, inputs, attrs, out=out)


@register
class SGD(Optimizer):
    """SGD with momentum + multi-precision (parity: optimizer.py SGD;
    fused ops sgd_update/sgd_mom_update/mp_* from optimizer_op.cc)."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype)
        return None

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and weight.dtype in (np.float16, np.dtype("bfloat16") if hasattr(np, "dtype") else ()):
            w32 = weight.astype(np.float32)
            return (self.create_state(index, w32), w32)
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        from .ndarray.sparse import RowSparseNDArray, sgd_lazy_update
        if isinstance(grad, RowSparseNDArray) and self.lazy_update:
            # lazy sparse path: touch only the gradient's rows (parity:
            # reference sgd FComputeEx lazy_update, optimizer.py:511)
            self._update_count(index)
            sgd_lazy_update(weight, grad, state, self._get_lr(index),
                            self._get_wd(index), self.momentum,
                            self.rescale_grad, self.clip_gradient)
            return
        if isinstance(grad, RowSparseNDArray):
            grad = grad.todense()
        self._update_count(index)
        attrs = self._common_attrs(self._get_lr(index), self._get_wd(index))
        if state is not None:
            attrs["momentum"] = self.momentum
            _invoke("sgd_mom_update", [weight, grad, state], attrs, weight)
        else:
            _invoke("sgd_update", [weight, grad], attrs, weight)

    def update_multi_precision(self, index, weight, grad, state):
        if isinstance(index, (list, tuple)):
            return self._aggregated_update(index, weight, grad, state)
        use_mp = self.multi_precision and isinstance(state, tuple) and \
            len(state) == 2 and hasattr(state[1], "shape") and \
            state[1].shape == weight.shape
        if not use_mp:
            return self.update(index, weight, grad, state)
        self._update_count(index)
        attrs = self._common_attrs(self._get_lr(index), self._get_wd(index))
        mom, w32 = state
        if mom is not None:
            attrs["momentum"] = self.momentum
            _invoke("mp_sgd_mom_update", [weight, grad, mom, w32], attrs, weight)
        else:
            _invoke("mp_sgd_update", [weight, grad, w32], attrs, weight)

    fused_elementwise = True
    fused_multi_precision = True

    def fused_update(self, params, grads, states, lr_t, wd_t):
        """Whole-pytree functional SGD step for the fused train step.

        Mirrors ``sgd_update``/``sgd_mom_update``/``mp_sgd_*``
        (ops/_op_optimizer.py) bit for bit — same op order, same python-
        float constants for rescale/clip/momentum — with lr/wd arriving
        as traced scalars in the dtype the update computes in, elements
        of the step's two hyperparameter arrays (``fused_step.
        hyper_scalars``; no recompile across schedules; the mesh-fused
        fsdp layout passes per-element lr/wd VECTORS instead, which the
        same elementwise expressions broadcast through).
        The multi-precision branch is chosen per param from the state
        STRUCTURE, exactly like ``update_multi_precision``."""
        import jax.numpy as jnp
        rescale = self.rescale_grad
        clip = self.clip_gradient
        momentum = self.momentum
        new_params, new_states = [], []
        for w, g, s, lr, wd in zip(params, grads, states, lr_t, wd_t):
            use_mp = self.multi_precision and isinstance(s, tuple) and \
                len(s) == 2 and hasattr(s[1], "shape") and \
                tuple(s[1].shape) == tuple(w.shape)
            if use_mp:
                mom, w32 = s
                g32 = g.astype(jnp.float32) * rescale
                if clip is not None:
                    g32 = jnp.clip(g32, -clip, clip)
                if mom is not None:
                    nm = momentum * mom - lr * (g32 + wd * w32)
                    nw32 = w32 + nm
                    new_states.append((nm, nw32))
                else:
                    nw32 = w32 - lr * (g32 + wd * w32)
                    new_states.append((None, nw32))
                new_params.append(nw32.astype(w.dtype))
                continue
            gi = g * rescale
            if clip is not None:
                gi = jnp.clip(gi, -clip, clip)
            if s is not None:
                nm = momentum * s - lr * (gi + wd * w)
                new_params.append(w + nm)
                new_states.append(nm)
            else:
                new_params.append(w - lr * (gi + wd * w))
                new_states.append(None)
        return new_params, new_states

    def fused_static_signature(self):
        return super().fused_static_signature() + (self.momentum,)

    def _aggregated_update(self, indices, weights, grads, states):
        """One multi_sgd_* dispatch for N weights (optimizer_op.cc:320;
        list-typed update_multi_precision mirrors the reference SGD)."""
        from .ndarray.sparse import BaseSparseNDArray
        mp = [self.multi_precision and isinstance(s, tuple) and len(s) == 2
              and hasattr(s[1], "shape") for s in states]
        aggregatable = (not any(isinstance(g, BaseSparseNDArray)
                                for g in grads)) and \
            (all(mp) or not any(mp))
        if not aggregatable:
            for i, w, g, s in zip(indices, weights, grads, states):
                self.update_multi_precision(i, w, g, s)
            return
        for i in indices:
            self._update_count(i)
        lrs = tuple(self._get_lr(i) for i in indices)
        wds = tuple(self._get_wd(i) for i in indices)
        attrs = {"lrs": lrs, "wds": wds,
                 "rescale_grad": self.rescale_grad,
                 "num_weights": len(indices)}
        if self.clip_gradient is not None:
            attrs["clip_gradient"] = self.clip_gradient
        use_mom = self.momentum != 0.0
        if use_mom:
            attrs["momentum"] = self.momentum
        ins = []
        if all(mp):
            for w, g, s in zip(weights, grads, states):
                mom, w32 = s
                ins.extend([w, g] + ([mom] if use_mom else []) + [w32])
            op = "multi_mp_sgd_mom_update" if use_mom else "multi_mp_sgd_update"
        else:
            for w, g, s in zip(weights, grads, states):
                ins.extend([w, g] + ([s] if use_mom else []))
            op = "multi_sgd_mom_update" if use_mom else "multi_sgd_update"
        _invoke(op, ins, attrs, list(weights))


@register
class Signum(Optimizer):
    """signSGD / Signum (parity: optimizer.py Signum; Bernstein et al. 2018)."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype)
        return None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        attrs = self._common_attrs(self._get_lr(index), self._get_wd(index))
        if state is not None:
            attrs["momentum"] = self.momentum
            attrs["wd_lh"] = self.wd_lh
            _invoke("signum_update", [weight, grad, state], attrs, weight)
        else:
            _invoke("signsgd_update", [weight, grad], attrs, weight)


@register
class NAG(Optimizer):
    """Nesterov accelerated SGD (parity: optimizer.py NAG)."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype)
        return None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        attrs = self._common_attrs(self._get_lr(index), self._get_wd(index))
        if state is not None:
            attrs["momentum"] = self.momentum
            _invoke("nag_mom_update", [weight, grad, state], attrs, weight)
        else:
            _invoke("sgd_update", [weight, grad], attrs, weight)


@register
class Adam(Optimizer):
    """Adam (parity: optimizer.py Adam; fused adam_update)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        from .ndarray.sparse import RowSparseNDArray, adam_lazy_update
        if isinstance(grad, RowSparseNDArray) and self.lazy_update:
            self._update_count(index)
            mean, var = state
            adam_lazy_update(weight, grad, mean, var, self._get_lr(index),
                             self._get_wd(index), self.beta1, self.beta2,
                             self.epsilon, self._index_update_count[index],
                             self.rescale_grad, self.clip_gradient)
            return
        if isinstance(grad, RowSparseNDArray):
            grad = grad.todense()
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index)
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        lr *= math.sqrt(coef2) / coef1
        attrs = self._common_attrs(lr, self._get_wd(index))
        attrs.update(beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon)
        mean, var = state
        _invoke("adam_update", [weight, grad, mean, var], attrs, weight)

    fused_elementwise = True

    def fused_update(self, params, grads, states, lr_t, wd_t):
        """Whole-pytree functional Adam step (mirrors ``adam_update`` in
        ops/_op_optimizer.py bit for bit).  The bias-corrected lr is
        folded into ``lr_t`` host-side by ``fused_hyperparams`` — same
        f64 arithmetic as ``update`` — so the step count never bakes
        into the trace."""
        import jax.numpy as jnp
        if self.multi_precision:
            raise MXNetError(
                "Adam.fused_update does not implement the multi-precision "
                "master-weight wrapper; the per-param loop handles it")
        rescale = self.rescale_grad
        clip = self.clip_gradient
        b1, b2, eps = self.beta1, self.beta2, self.epsilon
        new_params, new_states = [], []
        for w, g, s, lr, wd in zip(params, grads, states, lr_t, wd_t):
            mean, var = s
            gi = g * rescale
            if clip is not None:
                gi = jnp.clip(gi, -clip, clip)
            gi = gi + wd * w
            m = b1 * mean + (1 - b1) * gi
            v = b2 * var + (1 - b2) * jnp.square(gi)
            new_params.append(w - lr * m / (jnp.sqrt(v) + eps))
            new_states.append((m, v))
        return new_params, new_states

    def fused_hyperparams(self, indices):
        lrs, wds = [], []
        for i in indices:
            t = self._index_update_count[i]
            lr = self._get_lr(i)
            lr *= math.sqrt(1.0 - self.beta2 ** t) / (1.0 - self.beta1 ** t)
            lrs.append(float(lr))
            wds.append(float(self._get_wd(i)))
        return lrs, wds

    def fused_static_signature(self):
        return super().fused_static_signature() + \
            (self.beta1, self.beta2, self.epsilon)


@register
class AdamW(Optimizer):
    """Adam with decoupled weight decay (parity: contrib/adamw.cc)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, eta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.eta = eta

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        attrs = self._common_attrs(self._get_lr(index), self._get_wd(index))
        attrs.update(beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon,
                     eta=self.eta)
        mean, var = state
        _invoke("adamw_update", [weight, grad, mean, var], attrs, weight)


@register
class AdaGrad(Optimizer):
    """AdaGrad (parity: optimizer.py AdaGrad; Duchi et al. 2011)."""

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        history = state
        history += grad * grad
        div = grad / ((history + self.float_stable_eps) ** 0.5)
        weight[:] = weight - lr * (div + wd * weight)


@register
class RMSProp(Optimizer):
    """RMSProp, centered (Graves'12) or plain (Tieleman & Hinton'12)
    (parity: optimizer.py RMSProp)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                    nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                    nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype))
        return nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        attrs = self._common_attrs(self._get_lr(index), self._get_wd(index))
        attrs.update(gamma1=self.gamma1, epsilon=self.epsilon)
        if self.centered:
            n, g, delta = state
            attrs["gamma2"] = self.gamma2
            _invoke("rmspropalex_update", [weight, grad, n, g, delta], attrs,
                    weight)
        else:
            _invoke("rmsprop_update", [weight, grad, state], attrs, weight)


@register
class AdaDelta(Optimizer):
    """AdaDelta (parity: optimizer.py AdaDelta; Zeiler 2012)."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        acc_g, acc_delta = state
        acc_g[:] = self.rho * acc_g + (1.0 - self.rho) * grad * grad
        current_delta = ((acc_delta + self.epsilon) ** 0.5 /
                         (acc_g + self.epsilon) ** 0.5) * grad
        acc_delta[:] = self.rho * acc_delta + \
            (1.0 - self.rho) * current_delta * current_delta
        weight[:] = weight - current_delta - wd * weight


@register
class Ftrl(Optimizer):
    """FTRL-proximal (parity: optimizer.py Ftrl; McMahan et al. 2013)."""

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        attrs = self._common_attrs(self._get_lr(index), self._get_wd(index))
        attrs.update(lamda1=self.lamda1, beta=self.beta)
        z, n = state
        _invoke("ftrl_update", [weight, grad, z, n], attrs, weight)


@register
class Adamax(Optimizer):
    """AdaMax — Adam with infinity norm (parity: optimizer.py Adamax)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index) / (1.0 - self.beta1 ** t)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        m_t, u_t = state
        m_t[:] = self.beta1 * m_t + (1.0 - self.beta1) * grad
        u_t[:] = nd.maximum(self.beta2 * u_t, grad.abs())
        weight[:] = weight - lr * m_t / u_t


@register
class Nadam(Optimizer):
    """Nesterov Adam (parity: optimizer.py Nadam; Dozat 2016)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        momentum_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        momentum_t_1 = self.beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self.schedule_decay))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        m_t, v_t = state
        grad_prime = grad / (1.0 - self.m_schedule)
        m_t[:] = self.beta1 * m_t + (1.0 - self.beta1) * grad
        v_t[:] = self.beta2 * v_t + (1.0 - self.beta2) * grad * grad
        m_t_prime = m_t / (1.0 - m_schedule_next)
        v_t_prime = v_t / (1.0 - self.beta2 ** t)
        m_t_bar = (1.0 - momentum_t) * grad_prime + momentum_t_1 * m_t_prime
        weight[:] = weight - lr * m_t_bar / ((v_t_prime ** 0.5) + self.epsilon)


@register
class SGLD(Optimizer):
    """Stochastic Gradient Langevin Dynamics (parity: optimizer.py SGLD)."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        noise = nd.random.normal(0, math.sqrt(lr), weight.shape,
                                 dtype=weight.dtype, ctx=weight.ctx)
        weight[:] = weight - lr / 2 * (grad + wd * weight) + noise


@register
class DCASGD(Optimizer):
    """Delay-Compensated ASGD (parity: optimizer.py DCASGD; Zheng et al. 2016)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        mom, previous_weight = state
        if mom:
            mom[:] = self.momentum * mom - lr * (
                grad + wd * weight +
                self.lamda * grad * grad * (weight - previous_weight))
            weight_delta = mom
        else:
            weight_delta = -lr * (grad + wd * weight + self.lamda *
                                  grad * grad * (weight - previous_weight))
        previous_weight[:] = weight
        weight[:] = weight + weight_delta


@register
class FTML(Optimizer):
    """FTML (parity: optimizer.py FTML; Zheng & Kwok 2017)."""

    def __init__(self, learning_rate=0.0025, beta1=0.6, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype),  # d
                nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype),  # v
                nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype))  # z

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        d, v, z = state
        v[:] = self.beta2 * v + (1.0 - self.beta2) * grad * grad
        d_t = (1.0 - self.beta1 ** t) / lr * \
            ((v / (1.0 - self.beta2 ** t)) ** 0.5 + self.epsilon)
        sigma_t = d_t - self.beta1 * d
        z[:] = self.beta1 * z + (1.0 - self.beta1) * grad - sigma_t * weight
        d[:] = d_t
        weight[:] = -z / d_t


@register
class LBSGD(SGD):
    """Large-batch SGD with LARS-style layer-wise adaptive rates
    (parity: optimizer.py LBSGD, simplified to the LARS core)."""

    # LARS computes trust ratios from host-side norms (asscalar below) —
    # that cannot trace into the fused one-dispatch step; stay on the loop
    fused_update = None

    def __init__(self, momentum=0.0, eta=0.001, **kwargs):
        kwargs.pop("multi_precision", None)
        super().__init__(momentum=momentum, **kwargs)
        self.eta = eta

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        w_norm = float(weight.norm().asscalar())
        g_norm = float((grad * self.rescale_grad).norm().asscalar())
        if w_norm > 0 and g_norm > 0:
            lars = self.eta * w_norm / (g_norm + wd * w_norm + 1e-9)
            lr = lr * min(lars, 1.0)
        attrs = {"lr": lr, "wd": wd, "rescale_grad": self.rescale_grad}
        if self.clip_gradient is not None:
            attrs["clip_gradient"] = self.clip_gradient
        if state is not None:
            attrs["momentum"] = self.momentum
            _invoke("sgd_mom_update", [weight, grad, state], attrs, weight)
        else:
            _invoke("sgd_update", [weight, grad], attrs, weight)


@register
class LAMB(Optimizer):
    """LAMB layer-wise adaptation for large-batch (reference exposes
    lamb_update_phase1/2 ops; You et al. 2019)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, lower_bound=None, upper_bound=None,
                 bias_correction=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lower_bound = lower_bound
        self.upper_bound = upper_bound
        self.bias_correction = bias_correction

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                nd.zeros(weight.shape, weight.ctx, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        mean, var = state
        attrs = {"beta1": self.beta1, "beta2": self.beta2,
                 "epsilon": self.epsilon, "wd": wd, "t": t,
                 "bias_correction": self.bias_correction,
                 "rescale_grad": self.rescale_grad}
        g = _ndmod.invoke("lamb_update_phase1", [weight, grad, mean, var], attrs)
        r1 = weight.norm()
        if self.lower_bound is not None:
            r1 = nd.maximum(r1, nd.full((1,), self.lower_bound, ctx=weight.ctx))
        if self.upper_bound is not None:
            r1 = nd.minimum(r1, nd.full((1,), self.upper_bound, ctx=weight.ctx))
        r2 = g.norm()
        r1v = float(r1.asscalar())
        r2v = float(r2.asscalar())
        ratio = r1v / (r2v + 1e-9) if r1v > 0 and r2v > 0 else 1.0
        weight[:] = weight - lr * ratio * g


@register
class Test(Optimizer):
    """Trivial optimizer for tests (parity: optimizer.py Test)."""

    def create_state(self, index, weight):
        return nd.zeros(weight.shape, weight.ctx)

    def update(self, index, weight, grad, state):
        weight[:] = weight + grad * self.rescale_grad
        state[:] = weight


ccSGD = SGD  # deprecated alias kept for API parity


class Updater:
    """KVStore-executed updater closure (parity: optimizer.py:1647)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}

    def __call__(self, index, grad, weight):
        if isinstance(index, (list, tuple)):
            # aggregated call (reference optimizer.py Updater: list-typed
            # index batches into one multi-tensor update)
            for i, w in zip(index, weight):
                self._ensure_state(i, w)
            if hasattr(self.optimizer, "_aggregated_update"):
                self.optimizer.update_multi_precision(
                    list(index), list(weight), list(grad),
                    [self.states[i] for i in index])
            else:
                # optimizer without multi-tensor support: unroll
                for i, w, g in zip(index, weight, grad):
                    self.optimizer.update_multi_precision(
                        i, w, g, self.states[i])
            return
        self._ensure_state(index, weight)
        self.optimizer.update_multi_precision(index, weight, grad,
                                              self.states[index])

    def _ensure_state(self, index, weight):
        if index not in self.states:
            self.states[index] = self.optimizer.create_state_multi_precision(
                index, weight)
            self.states_synced[index] = True
        elif not self.states_synced.get(index, True):
            self.states[index] = self.sync_state_context(
                self.states[index], weight.ctx)
            self.states_synced[index] = True

    def sync_state_context(self, state, context):
        from .ndarray import NDArray
        if isinstance(state, NDArray):
            return state.as_in_context(context)
        if isinstance(state, (tuple, list)):
            return type(state)(
                self.sync_state_context(i, context) for i in state)
        return state

    def set_states(self, states):
        states = pickle.loads(states)
        if isinstance(states, tuple) and len(states) == 2:
            self.states, self.optimizer = states
        else:
            self.states = states
        self.states_synced = dict.fromkeys(self.states.keys(), False)

    def get_states(self, dump_optimizer=False):
        return pickle.dumps((self.states, self.optimizer)
                            if dump_optimizer else self.states)


def get_updater(optimizer):
    return Updater(optimizer)
