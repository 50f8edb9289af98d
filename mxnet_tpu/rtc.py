"""Runtime kernel compilation (parity: python/mxnet/rtc.py CudaModule over
NVRTC, src/common/rtc.cc / include/mxnet/rtc.h:39).

TPU redesign: the runtime-compiled kernel language is **Pallas**, not CUDA
C. A module holds Python source defining Pallas kernel functions
(``def axpy(x_ref, y_ref, alpha): y_ref[...] += alpha * x_ref[...]``);
``get_kernel(name, signature)`` keeps the reference's C-style signature
string — ``const`` pointers are inputs, non-const pointers are mutated
in/out arrays, non-pointer args are scalars — and ``launch`` keeps the
reference's semantics: output NDArrays are updated in place.

Differences from the CUDA original, by design:
- ``block_dims``/``shared_mem`` are accepted and ignored: Pallas block
  mapping comes from BlockSpecs (default: one whole-array block per grid
  step), and scratch memory is declared in the kernel, not at launch.
- a grid with product > 1 requires the kernel to partition work itself
  via ``pl.program_id`` (full arrays are visible to every step); launch
  refuses non-grid-aware kernels on multi-step grids rather than
  silently re-running the whole computation per step.
- scalars are closed over statically (one compile per distinct value),
  the practical Pallas idiom for small launch constants.
- off-TPU backends run the kernel in interpret mode, so the same source
  is testable on the CPU mesh.
"""
from __future__ import annotations

import numpy as np

from .base import MXNetError

_DTYPES = {
    "float": np.float32, "float32": np.float32,
    "double": np.float64, "float64": np.float64,
    "half": np.float16, "float16": np.float16,
    "bfloat16": "bfloat16",
    "int": np.int32, "int32": np.int32,
    "int8": np.int8, "uint8": np.uint8,
}


class _Arg:
    __slots__ = ("name", "dtype", "is_ptr", "is_const")

    def __init__(self, name, dtype, is_ptr, is_const):
        self.name = name
        self.dtype = dtype
        self.is_ptr = is_ptr
        self.is_const = is_const


def _parse_signature(signature):
    """Parse the reference's C-style kernel signature (rtc.py get_kernel
    contract): 'const float *x, float *y, float alpha'."""
    args = []
    for raw in signature.split(","):
        toks = raw.replace("*", " * ").split()
        if not toks:
            continue
        is_const = toks[0] == "const"
        if is_const:
            toks = toks[1:]
        if not toks:
            raise MXNetError(f"cannot parse signature chunk {raw!r}")
        tname = toks[0]
        if tname not in _DTYPES:
            raise MXNetError(
                f"unknown dtype {tname!r} in signature chunk {raw!r}; "
                f"have {sorted(_DTYPES)}")
        rest = toks[1:]
        is_ptr = "*" in rest
        name = rest[-1] if rest and rest[-1] != "*" else tname
        args.append(_Arg(name, _DTYPES[tname], is_ptr, is_const))
    return args


class PallasKernel:
    """A launchable kernel (parity: rtc.py CudaKernel)."""

    def __init__(self, fn, name, sig_args, grid_aware=False):
        self._fn = fn
        self.name = name
        self._args = sig_args
        self._n_tensors = sum(1 for a in sig_args if a.is_ptr)
        # whether the source indexes by pl.program_id — see launch()
        self._grid_aware = grid_aware
        self._compile_cache = {}

    def _compiled(self, grid, out_meta, scalars, interpret):
        ck = (grid, out_meta, scalars, interpret)
        cached = self._compile_cache.get(ck)
        if cached is not None:
            return cached
        from jax.experimental import pallas as pl
        import jax

        scalar_vals = dict(scalars)
        tensor_slots = [a for a in self._args if a.is_ptr]
        out_slots = [i for i, a in enumerate(tensor_slots) if not a.is_const]

        def kernel(*refs):
            # rebuild the declared argument order: refs for pointers
            # (inputs then outputs, aliased), closed-over scalars else.
            # pallas passes inputs first then outputs; inputs include the
            # aliased in/out arrays, whose output refs are authoritative.
            ins = refs[:self._n_tensors]
            outs = refs[self._n_tensors:]
            call = []
            out_i = 0
            for j, a in enumerate(self._args):
                if not a.is_ptr:
                    call.append(scalar_vals[a.name])
                elif a.is_const:
                    call.append(ins[[t.name for t in tensor_slots
                                     ].index(a.name)])
                else:
                    call.append(outs[out_i])
                    out_i += 1
            self._fn(*call)

        out_shapes = [jax.ShapeDtypeStruct(s, d) for s, d in out_meta]
        aliases = {out_slots[k]: k for k in range(len(out_slots))}
        fn = pl.pallas_call(
            kernel,
            out_shape=out_shapes,
            grid=grid,  # () = single program, the default for full-array blocks
            input_output_aliases=aliases,
            interpret=interpret,
        )
        self._compile_cache[ck] = fn
        return fn

    def launch(self, args, ctx, grid_dims, block_dims=None, shared_mem=0):
        """Run the kernel (parity: rtc.py CudaKernel.launch). Non-const
        pointer args are updated in place; grid_dims maps to the Pallas
        grid (trailing 1s dropped); block_dims/shared_mem are accepted
        for source compatibility and ignored (see module docstring)."""
        del block_dims, shared_mem
        from .ndarray import NDArray
        import jax

        if len(args) != len(self._args):
            raise MXNetError(
                f"kernel {self.name!r} declares {len(self._args)} args "
                f"({', '.join(a.name for a in self._args)}); launch got "
                f"{len(args)}")
        tensors, scalars = [], []
        for a, v in zip(self._args, args):
            if a.is_ptr:
                if not isinstance(v, NDArray):
                    raise MXNetError(
                        f"kernel arg {a.name!r} is a pointer; expected "
                        f"NDArray, got {type(v).__name__}")
                want = ("bfloat16" if a.dtype == "bfloat16"
                        else np.dtype(a.dtype).name)
                got = np.dtype(v.dtype).name
                if got != want:
                    raise MXNetError(
                        f"kernel arg {a.name!r} declared {want} but the "
                        f"NDArray is {got} (the reference launch rejects "
                        "dtype mismatches too)")
                tensors.append(v)
            else:
                scalars.append((a.name, np.dtype(a.dtype).type(v)
                                if a.dtype != "bfloat16" else float(v)))
        grid = tuple(int(g) for g in grid_dims)
        while grid and grid[-1] == 1:
            grid = grid[:-1]
        if grid and int(np.prod(grid)) > 1 and not self._grid_aware:
            # without BlockSpecs every grid step sees the FULL arrays; a
            # CUDA-style kernel that doesn't index by pl.program_id would
            # silently run the whole computation prod(grid) times (fatal
            # for accumulating kernels like axpy's +=)
            raise MXNetError(
                f"kernel {self.name!r} launched with grid {grid} but its "
                "source never uses pl.program_id: each grid step would "
                "re-run the whole-array kernel. Index your refs by "
                "pl.program_id(axis) to partition work, or launch with "
                "a product-1 grid.")
        outs = [t for t, a in zip(tensors, (x for x in self._args
                                            if x.is_ptr))
                if not a.is_const]
        out_meta = tuple((tuple(t.shape), np.dtype(t.dtype)) for t in outs)
        # Mosaic where the operands live on a tpu device, the Pallas
        # interpreter anywhere else (a host-resident array in a process
        # whose default backend is the chip still interprets)
        interpret = any(d.platform != "tpu"
                        for t in tensors for d in t._data.devices())
        fn = self._compiled(grid, out_meta, tuple(scalars), interpret)
        results = fn(*[t._data for t in tensors])
        if not isinstance(results, (list, tuple)):
            results = [results]
        for t, r in zip(outs, results):
            t._set_data(r)  # in-place update semantics + version bump
        return outs


class PallasModule:
    """Compile Pallas kernel source at runtime (parity: rtc.py
    CudaModule; the NVRTC role is played by exec + pallas_call)."""

    def __init__(self, source, options=(), exports=()):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        ns = {"jax": jax, "jnp": jnp, "pl": pl, "np": np}
        try:
            exec(compile(source, "<mx.rtc>", "exec"), ns, ns)
        except SyntaxError as e:
            raise MXNetError(f"rtc source failed to compile: {e}") from e
        self._ns = ns
        self._source = source
        self.exports = tuple(exports) or tuple(
            k for k, v in ns.items() if callable(v)
            and getattr(v, "__module__", None) is None)

    def _kernel_source(self, name):
        """Source segment of one kernel function (for the per-kernel
        grid_aware check — a sibling kernel's program_id use must not
        vouch for this one)."""
        import ast
        try:
            tree = ast.parse(self._source)
            for node in ast.walk(tree):
                if isinstance(node, ast.FunctionDef) and node.name == name:
                    return ast.get_source_segment(self._source, node) or ""
        except SyntaxError:
            pass
        return self._source  # unparseable: fall back to whole-module scan

    def get_kernel(self, name, signature):
        fn = self._ns.get(name)
        if fn is None or not callable(fn):
            raise MXNetError(f"no kernel {name!r} in module "
                             f"(defined: {sorted(self.exports)})")
        return PallasKernel(
            fn, name, _parse_signature(signature),
            grid_aware="program_id" in self._kernel_source(name))


# source-compat alias: scripts using mx.rtc.CudaModule keep working, the
# kernel language is Pallas here
CudaModule = PallasModule
