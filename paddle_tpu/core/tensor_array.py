"""TensorArray + hierarchical Scope + typed errors.

Parity:
- TensorArray: reference phi/core/tensor_array.h / LoDTensorArray and
  the python array ops (python/paddle/tensor/array.py: create_array,
  array_write, array_read, array_length) used by while_loop bodies.
- Scope: reference paddle/fluid/framework/scope.h — hierarchical
  name->Variable maps with parent lookup; Executor runs against a scope.
- errors: reference PADDLE_ENFORCE error hierarchy
  (phi/core/enforce.h + platform/errors.h: InvalidArgument, NotFound,
  OutOfRange, Unimplemented, ...) surfaced as typed python exceptions.

TPU-native: a TensorArray used inside a compiled while_loop must become
a fixed-shape stacked buffer (XLA has no dynamic lists); eager mode
keeps the python list. to_static's lax lowering uses stack()/unstack.
"""
from __future__ import annotations

import jax.numpy as jnp

from .tensor import Tensor


class TensorArray:
    """Dynamic array of tensors (eager); stack() produces the XLA-ready
    fixed buffer."""

    def __init__(self, values=None):
        self._items = list(values or [])

    def append(self, t):
        self._items.append(t if isinstance(t, Tensor) else Tensor(t))
        return self

    def write(self, i, t):
        t = t if isinstance(t, Tensor) else Tensor(t)
        if i == len(self._items):
            self._items.append(t)
        else:
            self._items[i] = t
        return self

    def read(self, i):
        return self._items[i]

    def pop(self, i=-1):
        return self._items.pop(i)

    def __len__(self):
        return len(self._items)

    def __getitem__(self, i):
        return self._items[i]

    def stack(self, axis=0):
        return Tensor(jnp.stack([t._value for t in self._items],
                                axis=axis))

    @classmethod
    def unstack(cls, t, axis=0):
        v = t._value if isinstance(t, Tensor) else jnp.asarray(t)
        n = v.shape[axis]
        return cls([Tensor(jnp.squeeze(s, axis))
                    for s in jnp.split(v, n, axis=axis)])


# array op API (reference python/paddle/tensor/array.py)

def create_array(dtype=None, initialized_list=None):
    return TensorArray(initialized_list)


def array_write(x, i, array=None):
    if array is None:
        array = TensorArray()
    array.write(int(i), x)
    return array


def array_read(array, i):
    return array.read(int(i))


def array_length(array):
    return len(array)


def tensor_array_to_tensor(array, axis=0, use_stack=True):
    if use_stack:
        return array.stack(axis), len(array)
    vals = [t._value for t in array._items]
    return Tensor(jnp.concatenate(vals, axis=axis)), len(array)


# -- Scope -------------------------------------------------------------------

class Variable_:
    """Scope-held slot (reference framework/variable.h): wraps whatever
    it stores (Tensor / TensorArray / SelectedRows / bytes). A slot can
    alternatively *bind* a live framework Tensor (weakly): the base
    global scope mirrors program state this way, so reading through the
    scope always sees the current value without pinning dead programs'
    arrays alive."""

    def __init__(self, name):
        self.name = name
        self._holder = None
        self._tensor_ref = None

    def get_tensor(self):
        if self._tensor_ref is not None:
            t = self._tensor_ref()
            return None if t is None else t._value
        return self._holder

    def set(self, value):
        self._holder = value
        self._tensor_ref = None
        return self

    def bind(self, tensor):
        import weakref

        self._holder = None
        self._tensor_ref = weakref.ref(tensor)
        return self

    def is_initialized(self):
        if self._tensor_ref is not None:
            return self._tensor_ref() is not None
        return self._holder is not None


class Scope:
    """Hierarchical name->Variable map (reference scope.h): find_var
    searches ancestors; var() creates locally."""

    def __init__(self, parent=None):
        self._vars = {}
        self._parent = parent
        self._kids = []
        # per-program executor runtime state (optimizer slots, grad-merge
        # accumulators, step counter) when this scope is the run target —
        # reference scopes likewise own the optimizer accumulator
        # variables. Weakly keyed by the Program object so a dead
        # program's state is released (and a recycled id can never
        # resurrect it).
        import weakref

        self._exec_state = weakref.WeakKeyDictionary()

    def var(self, name):
        v = self._vars.get(name)
        if v is None:
            v = Variable_(name)
            self._vars[name] = v
        return v

    def find_var(self, name):
        v = self._vars.get(name)
        if v is not None:
            return v
        if self._parent is not None:
            return self._parent.find_var(name)
        return None

    def _find_var_with_owner(self, name):
        """(Variable_, owning Scope) through the ancestor chain, or
        (None, None) — the Executor needs the owner to tell real storage
        apart from the base scope's tensor-backed mirror vars."""
        v = self._vars.get(name)
        if v is not None:
            return v, self
        if self._parent is not None:
            return self._parent._find_var_with_owner(name)
        return None, None

    def new_scope(self):
        kid = Scope(self)
        self._kids.append(kid)
        return kid

    def drop_kids(self):
        self._kids.clear()

    def local_var_names(self):
        return sorted(self._vars)

    def erase(self, names):
        for n in names:
            self._vars.pop(n, None)


_global_scope = Scope()
# the process-default scope: its variables are backed by the program
# tensors themselves (tensor storage is canonical there); every other
# scope holds its own copies so Executor runs under it stay isolated
_BASE_SCOPE = _global_scope


def global_scope():
    return _global_scope


def is_base_scope(scope):
    return scope is _BASE_SCOPE


def scope_guard(scope):
    """Context manager swapping the global scope (reference
    paddle.static.scope_guard)."""
    import contextlib

    @contextlib.contextmanager
    def guard():
        global _global_scope
        prev = _global_scope
        _global_scope = scope
        try:
            yield scope
        finally:
            _global_scope = prev

    return guard()
