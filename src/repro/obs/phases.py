"""The device phases of a DP step, as named scopes in the compiled program.

The host spans of :mod:`.metrics` time what the host dispatched; inside
one jitted ``accumulate`` the device runs every phase of the paper's
Table 2 back to back, where no host span can see.  A named scope does:
``jax.named_scope`` puts its name into the ``op_name`` metadata of every
HLO instruction traced under it, and the profiler's device trace names
those instructions, so device time can be summed per phase.  Scopes are
compile-time metadata only: they add no device work and no host work on
any step.

The vocabulary is fixed, so that readers of the metadata and the engines
agree on it:

* ``grad/per_example`` — the vmapped per-example forward and backward
  (:func:`repro.core.clipping.per_example_grads_and_sq`);
* ``grad/norm_pass`` — ghost clipping's norm pass: the ε-tapped forward,
  the backward to the taps and the per-layer norm contractions
  (:func:`repro.core.clipping.ghost_norms`);
* ``grad/reweighted`` — ghost clipping's second forward and backward,
  weighted by the clip coefficients;
* ``grad/batch`` — the non-private batch gradient;
* ``grad/accumulate`` — everything from a gradient to the accumulator:
  the clip coefficients, the flat tile and its Pallas fold, the cast and
  the add into ``TrainState.grad_acc``.

Scopes wrap call sites in ``core/``, never code inside ``kernels/``: a
scope between ``jit(<kernel>)`` and ``pallas_call`` would change the
kernel's ``op_name`` path, which is how the kernel is found in the HLO.

A second fixed vocabulary, :data:`BLOCKS`, names the model blocks whose
time is worth splitting inside a phase; the model code wraps them with
:func:`block`.  A block scope sits inside whichever phase runs the model
(the per-example backward, the norm pass, the reweighted backward), so it
never changes the phase an instruction is credited to:

* ``mla/attention`` — latent attention (:func:`repro.models.mla.mla_attention`
  and its decode form);
* ``moe/route`` — the router's logits, softmax, top-k, the capacity
  positions and the load-balance term;
* ``moe/dispatch`` — tokens scattered into the held experts' capacity
  buffers;
* ``moe/experts`` — the held experts' SwiGLU matmuls;
* ``moe/combine`` — the experts' outputs gathered back and weighted by
  the gates.
"""
from __future__ import annotations

PHASES = ("grad/per_example", "grad/norm_pass", "grad/reweighted",
          "grad/batch", "grad/accumulate")

BLOCKS = ("mla/attention", "moe/route", "moe/dispatch", "moe/experts",
          "moe/combine")


def _scope(name: str, vocabulary, kind: str):
    if name not in vocabulary:
        raise ValueError(f"unknown {kind} {name!r}; {kind}s: {vocabulary}")
    import jax
    return jax.named_scope(name)


def phase(name: str):
    """``jax.named_scope(name)`` for a phase of :data:`PHASES`; any other
    name is refused."""
    return _scope(name, PHASES, "phase")


def block(name: str):
    """``jax.named_scope(name)`` for a model block of :data:`BLOCKS`; any
    other name is refused."""
    return _scope(name, BLOCKS, "block")
