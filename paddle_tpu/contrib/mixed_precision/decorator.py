"""AMP optimizer decorator.

Parity: /root/reference/python/paddle/fluid/contrib/mixed_precision/decorator.py
(decorate:218, OptimizerWithMixedPrecision:27, backward:112).

bfloat16 is the TPU default (no loss scaling: bf16 has the f32 exponent
range). float16 mode keeps the reference's dynamic loss-scaling protocol:
scale the loss, unscale grads, detect inf/nan, grow/shrink the scale, and
zero the grads on overflow so the whole step stays one XLA program
(branch-free; the reference conditionally skips the update instead).
"""
from __future__ import annotations

from typing import Optional

from ...fluid import framework, layers
from ...fluid.initializer import ConstantInitializer
from .fp16_lists import AutoMixedPrecisionLists
from .fp16_utils import rewrite_program


class OptimizerWithMixedPrecision:
    def __init__(
        self,
        optimizer,
        amp_lists: Optional[AutoMixedPrecisionLists] = None,
        init_loss_scaling: float = 2.0 ** 15,
        use_dynamic_loss_scaling: bool = True,
        incr_every_n_steps: int = 1000,
        decr_every_n_nan_or_inf: int = 2,
        incr_ratio: float = 2.0,
        decr_ratio: float = 0.8,
        use_bf16: bool = True,
    ):
        self._optimizer = optimizer
        self._amp_lists = amp_lists or AutoMixedPrecisionLists()
        self._dest_dtype = "bfloat16" if use_bf16 else "float16"
        self._use_dynamic_loss_scaling = use_dynamic_loss_scaling and not use_bf16
        self._init_loss_scaling = init_loss_scaling if not use_bf16 else 1.0
        self._incr_every_n_steps = incr_every_n_steps
        self._decr_every_n_nan_or_inf = decr_every_n_nan_or_inf
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._loss_scaling = None

    def get_loss_scaling(self):
        return self._loss_scaling

    def _create_scaling_state(self):
        def persist(name, value):
            main_block = framework.default_main_program().global_block()
            v = main_block.create_var(
                name=name, shape=(1,), dtype="float32", persistable=True
            )
            sblock = framework.default_startup_program().global_block()
            sv = sblock.create_var(
                name=name, shape=(1,), dtype="float32", persistable=True
            )
            ConstantInitializer(value)(sv, sblock)
            return v

        from ...fluid import unique_name

        self._loss_scaling = persist(
            unique_name.generate("loss_scaling"), self._init_loss_scaling
        )
        if self._use_dynamic_loss_scaling:
            self._good_steps = persist(unique_name.generate("good_steps"), 0.0)
            self._bad_steps = persist(unique_name.generate("bad_steps"), 0.0)
            # numerics observability (ISSUE 12): the scale var already
            # rides the step's state outputs, so growth/backoff events
            # become countable host-side without any graph change —
            # numerics_amp_scale_{growths,backoffs}_total counters +
            # kind="numerics" amp_scale sink records with step numbers
            from ...telemetry import numerics as _numerics

            _numerics.register_amp_scale(
                self._loss_scaling.name,
                good_name=self._good_steps.name,
                bad_name=self._bad_steps.name)

    def backward(self, loss, startup_program=None, parameter_list=None, no_grad_set=None, callbacks=None):
        program = loss.block.program
        # fuse BEFORE the cast rewrite: the matcher sees the raw
        # conv2d->batch_norm[->relu] triples, and the fused op then takes
        # its own white-list casts (Input/Filter bf16, stats kept f32)
        from ...fluid.fusion_pass import maybe_apply_conv_bn_fusion

        maybe_apply_conv_bn_fusion(program)
        rewrite_program(program, self._amp_lists, self._dest_dtype)
        self._create_scaling_state()
        with framework.program_guard(program, startup_program or framework.default_startup_program()):
            scaled_loss = layers.elementwise_mul(loss, self._loss_scaling)
        params_grads = self._optimizer.backward(
            scaled_loss, startup_program, parameter_list, no_grad_set, callbacks
        )
        return scaled_loss, params_grads

    def apply_gradients(self, params_grads):
        # the float32 casts of the gradients, the unscale, the finite
        # check and the loss-scale update are the optimizer's part of the
        # step (framework.Operator.role)
        program = params_grads[0][0].block.program
        with program._optimized_guard():
            return self._apply(params_grads)

    def _apply(self, params_grads):
        if self._dest_dtype == "bfloat16" and not self._use_dynamic_loss_scaling:
            # bf16 has f32 exponent range: scale stays 1.0 and overflow
            # can't occur from the cast itself, so the unscale +
            # found_inf pass (a full extra read of every gradient) is
            # pure overhead — feed f32 grads straight to the optimizer
            with framework.program_guard(
                params_grads[0][0].block.program,
                framework.default_startup_program(),
            ):
                final = []
                for p, g in params_grads:
                    if g is not None and str(g.dtype) != "float32":
                        g = layers.cast(g, "float32")
                    final.append((p, g))
                return self._optimizer.apply_gradients(final)
        grads = [g for _, g in params_grads if g is not None]
        with framework.program_guard(
            params_grads[0][0].block.program, framework.default_startup_program()
        ):
            inv = layers.elementwise_div(
                layers.fill_constant([1], "float32", 1.0), self._loss_scaling
            )
            # found_inf = any grad non-finite (after cast to f32)
            found_inf = layers.fill_constant([1], "bool", 0.0)
            new_pgs = []
            for p, g in params_grads:
                if g is None:
                    new_pgs.append((p, g))
                    continue
                g32 = layers.cast(g, "float32") if str(g.dtype) != "float32" else g
                bad = layers.logical_not(
                    layers.reduce_all(layers.isfinite_v2(g32))
                )
                found_inf = layers.logical_or(found_inf, bad)
                new_pgs.append((p, g32))
            keep = layers.cast(layers.logical_not(found_inf), "float32")
            zero = layers.fill_constant([1], "float32", 0.0)
            final = []
            for p, g in new_pgs:
                if g is None:
                    final.append((p, g))
                    continue
                # zero-on-overflow must SELECT, not multiply: inf * 0
                # is NaN, so the old keep-multiply poisoned the params
                # with NaN on the very overflow step it meant to skip
                # (found while unifying the bad-step guard, ISSUE 12).
                # where() drops the non-finite entries first; the keep
                # factor then kills the rest of the overflowed step.
                g = layers.where(layers.isfinite_v2(g), g, zero)
                g = layers.elementwise_mul(g, layers.elementwise_mul(inv, keep))
                final.append((p, g))
            if self._use_dynamic_loss_scaling:
                self._update_loss_scaling(found_inf)
            return self._optimizer.apply_gradients(final)

    def _update_loss_scaling(self, found_inf):
        """Branch-free grow/shrink of the scale (reference
        fp16_utils.update_loss_scaling:333 semantics)."""
        bad = layers.cast(found_inf, "float32")
        good = layers.scale(bad, scale=-1.0, bias=1.0)
        # counters
        new_good = layers.elementwise_mul(
            layers.increment(self._good_steps, 1.0, in_place=False), good
        )
        new_bad = layers.elementwise_mul(
            layers.increment(self._bad_steps, 1.0, in_place=False), bad
        )
        grow = layers.cast(
            layers.greater_equal(
                new_good, layers.fill_constant([1], "float32", float(self._incr_every_n_steps))
            ),
            "float32",
        )
        shrink = layers.cast(
            layers.greater_equal(
                new_bad, layers.fill_constant([1], "float32", float(self._decr_every_n_nan_or_inf))
            ),
            "float32",
        )
        factor = (
            1.0
            + grow * (self._incr_ratio - 1.0)
        )
        factor = layers.elementwise_mul(
            factor, layers.scale(shrink, scale=self._decr_ratio - 1.0, bias=1.0)
        )
        new_scale = layers.elementwise_mul(self._loss_scaling, factor)
        layers.assign(new_scale, self._loss_scaling)
        from ...fluid.flags import flag as _flag

        if _flag("FLAGS_check_numerics"):
            # unified bad-step guard (ISSUE 12): FLAGS_check_numerics
            # used to watch fp32 grads only while AMP kept its own
            # zero-and-shrink protocol with no terminal condition. Here
            # an overflow step that pushes the scale BELOW the floor
            # (FLAGS_check_numerics_amp_scale_floor) means backoff is
            # EXHAUSTED — the model produces non-finite values at any
            # scale — so a check_numerics_bad_amp_* guard var trips,
            # Executor.run raises BadStepError and the NaN-provenance
            # doctor dumps a numrec for the AMP run too. Transient
            # overflows (scale still above the floor) keep AMP's skip
            # semantics: the guard stays 0 and training continues.
            from ...fluid import unique_name as _un
            from ...fluid.initializer import ConstantInitializer as _CI

            floor = float(_flag("FLAGS_check_numerics_amp_scale_floor"))
            floor_c = layers.fill_constant([1], "float32", floor)
            exhausted = layers.logical_and(
                found_inf, layers.less_than(new_scale, floor_c))
            name = _un.generate("check_numerics_bad_amp")
            main_block = framework.default_main_program().global_block()
            guard = main_block.create_var(
                name=name, shape=(1,), dtype="float32",
                persistable=True, stop_gradient=True)
            sblock = framework.default_startup_program().global_block()
            sv = sblock.create_var(
                name=name, shape=(1,), dtype="float32", persistable=True)
            _CI(0.0)(sv, sblock)
            layers.assign(layers.cast(exhausted, "float32"), guard)
        # reset counters when they fire
        layers.assign(
            layers.elementwise_mul(new_good, layers.scale(grow, scale=-1.0, bias=1.0)),
            self._good_steps,
        )
        layers.assign(
            layers.elementwise_mul(new_bad, layers.scale(shrink, scale=-1.0, bias=1.0)),
            self._bad_steps,
        )

    def apply_optimize(self, loss, startup_program, params_grads):
        """Same contract as Optimizer.apply_optimize — THIS level's
        apply_gradients (unscale/f32-cast), not the inner's. Lets
        backward-then-apply callers (fleet's hybrid_dcn wrappers, which
        insert c_dcn_grad_sync between the two) compose with AMP without
        __getattr__ silently bypassing the gradient post-processing."""
        with framework.program_guard(
            loss.block.program,
            startup_program or framework.default_startup_program(),
        ):
            return self.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None, no_grad_set=None):
        scaled_loss, params_grads = self.backward(
            loss, startup_program, parameter_list, no_grad_set
        )
        with framework.program_guard(
            loss.block.program,
            startup_program or framework.default_startup_program(),
        ):
            optimize_ops = self.apply_gradients(params_grads)
        return optimize_ops, params_grads

    def __getattr__(self, item):
        return getattr(self._optimizer, item)


def decorate(
    optimizer,
    amp_lists=None,
    init_loss_scaling=2.0 ** 15,
    incr_every_n_steps=1000,
    decr_every_n_nan_or_inf=2,
    incr_ratio=2.0,
    decr_ratio=0.8,
    use_dynamic_loss_scaling=True,
    use_bf16=True,
):
    """reference decorator.py:218 — wrap an optimizer with AMP."""
    return OptimizerWithMixedPrecision(
        optimizer,
        amp_lists=amp_lists,
        init_loss_scaling=init_loss_scaling,
        use_dynamic_loss_scaling=use_dynamic_loss_scaling,
        incr_every_n_steps=incr_every_n_steps,
        decr_every_n_nan_or_inf=decr_every_n_nan_or_inf,
        incr_ratio=incr_ratio,
        decr_ratio=decr_ratio,
        use_bf16=use_bf16,
    )
