"""StandardWorkflow: build a full training workflow from a config layer
list.

Reference parity: Znicz's ``StandardWorkflow`` wired
loader→forwards→evaluator→decision→gradient-units→plotters from a config
layer list (reference: docs manualrst_veles_workflow_creation.rst;
SURVEY.md §2.10). Here gradient units don't exist (autodiff), so the factory
wires loader→forwards→evaluator and pairs with a Trainer.

Layer dicts: ``{"type": "conv_relu", "n_kernels": 96, "kx": 11, ...}``;
``type`` resolves through LAYER_TYPES; ``inputs`` (a list of unit names or
batch keys) replaces the default of the layer before. The per-layer ``hyperparams`` key
lands in the optimizer's per-unit table (per-layer lr/momentum/l2 —
reference item docs manualrst_veles_algorithms.rst:166).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from ..ops.optimizers import HyperParams, OPTIMIZERS, Optimizer
from ..units import linear_attention, nn, parallel_nn, recurrent, ssm
from ..units.workflow import Workflow

LAYER_TYPES = {
    # parallelism-aware units (sp/pp/ep as config-constructible features)
    "attention": parallel_nn.MultiHeadAttention,
    "moe": parallel_nn.MoEFFN,
    "routed_experts": parallel_nn.RoutedExpertsFFN,
    "pipeline_stack": parallel_nn.PipelineStack,
    # recurrent family (reference: Znicz RNN/LSTM "created but not
    # tested", manualrst_veles_algorithms.rst:115-134 — here tested)
    "rnn": recurrent.RNN,
    "gru": recurrent.GRU,
    "lstm": recurrent.LSTM,
    "all2all": nn.All2All,
    "all2all_tanh": nn.All2AllTanh,
    "all2all_relu": nn.All2AllRELU,
    "all2all_sincos": nn.All2AllSincos,
    "softmax": nn.All2AllSoftmax,
    "conv": nn.Conv,
    "conv_relu": nn.ConvRELU,
    "conv_tanh": nn.ConvTanh,
    "deconv": nn.Deconv,
    "max_pooling": nn.MaxPooling,
    "avg_pooling": nn.AvgPooling,
    "stochastic_abs_pooling": nn.StochasticAbsPooling,
    "depool": nn.Depool,
    "dropout": nn.Dropout,
    "lrn": nn.LRN,
    "norm": nn.MeanDispNormalizer,
    "flatten": nn.Flatten,
    "reshape": nn.Reshape,
    "embedding": nn.Embedding,
    "ffn": nn.FFN,
    "layer_norm": nn.LayerNorm,
    "rms_norm": nn.RMSNorm,
    "gated_mlp": nn.GatedMLP,
    "add": nn.Add,
    "mamba2": ssm.Mamba2Mixer,
    "gated_delta_net": linear_attention.GatedDeltaNet,
    "kimi_delta_attention": linear_attention.KimiDeltaAttention,
    "seq_last": nn.SeqLast,
}


# layer-type prefixes that take a compute_dtype kwarg (the MXU-bf16
# switch); shared with PipelineStack's stage-config builder
COMPUTE_DTYPE_TYPES = ("all2all", "softmax", "conv", "deconv", "rnn",
                       "gru", "lstm", "attention", "ffn", "gated_mlp",
                       "routed_experts", "mamba2", "gated_delta_net",
                       "kimi_delta_attention")


def build_workflow(name: str, layers: Sequence[dict], *,
                   loss: str = "softmax",
                   compute_dtype: Optional[str] = None) -> Workflow:
    """Construct a Workflow from a layer-config list.

    ``loss``: "softmax" -> EvaluatorSoftmax on (@labels, @mask);
              "mse"     -> EvaluatorMSE on (@targets, @mask);
              "mse_input" -> EvaluatorMSE against @input (autoencoders).
    """
    wf = Workflow(name)
    prev = "@input"
    for i, spec in enumerate(layers):
        spec = dict(spec)
        ltype = spec.pop("type")
        spec.pop("hyperparams", None)
        lname = spec.pop("name", f"l{i}_{ltype}")
        # a layer follows the one before it unless it names its inputs
        # (unit names or batch keys): a second input is how a block's
        # residual stream reaches the unit that adds to it
        inputs = tuple(spec.pop("inputs", (prev,)))
        # activation rematerialization knob: the training forward wraps
        # this unit in jax.checkpoint, recomputing its internals in the
        # backward instead of taping them (HBM-for-FLOPs trade — the
        # standard lever for deep stacks that don't fit; numerics are
        # identical, tests/test_workflow.py asserts grad exactness).
        # pipeline_stack bodies are ALREADY rematerialized by both
        # schedules — an outer checkpoint would recompute stages twice
        # for no memory benefit, so the flag is dropped there.
        remat = bool(spec.pop("remat", False)) \
            and ltype != "pipeline_stack"
        klass = LAYER_TYPES[ltype]
        if compute_dtype is not None and ltype.startswith(
                COMPUTE_DTYPE_TYPES + ("pipeline_stack",)):
            # pipeline_stack forwards compute_dtype into its stage
            # sublists (only to unit types that take it)
            spec.setdefault("compute_dtype", compute_dtype)
        unit = klass(name=lname, inputs=inputs, **spec)
        unit.remat = remat
        wf.add(unit)
        prev = lname

    if loss == "softmax":
        wf.add(nn.EvaluatorSoftmax(name="evaluator",
                                   inputs=(prev, "@labels", "@mask")))
    elif loss == "mse":
        wf.add(nn.EvaluatorMSE(name="evaluator",
                               inputs=(prev, "@targets", "@mask")))
    elif loss == "mse_input":
        wf.add(nn.EvaluatorMSE(name="evaluator",
                               inputs=(prev, "@input", "@mask")))
    elif loss == "none":
        pass
    else:
        raise ValueError(f"unknown loss {loss!r}")
    return wf


def build_optimizer(kind: str, layers: Sequence[dict],
                    **kwargs) -> Optimizer:
    """Optimizer from name + per-layer hyperparams gathered off the layer
    configs (the reference's per-gradient-unit settings).

    ``lr_policy`` may be a config dict — ``{"type": "exp"|"inv"|"step"|
    "fixed", ...args}`` — resolved through ops.optimizers.LR_POLICIES, so
    JSON workflow configs can express the reference's lr adjust policies
    (docs manualrst_veles_algorithms.rst:156 item 3)."""
    policy = kwargs.get("lr_policy")
    if isinstance(policy, dict):
        import inspect

        from ..ops.optimizers import LR_POLICIES
        p = dict(policy)
        ptype = p.pop("type")
        if "base" not in p and "lr" not in kwargs:
            # fall back to the optimizer's OWN lr default (AdaDelta is
            # 1.0, Adam 1e-3 — a flat 0.01 would silently rescale them)
            sig = inspect.signature(OPTIMIZERS[kind]).parameters.get("lr")
            if sig is not None and sig.default is not inspect.Parameter.empty:
                p["base"] = sig.default
        p.setdefault("base", kwargs.get("lr", 0.01))
        kwargs["lr_policy"] = LR_POLICIES[ptype](**p)
    per_unit: Dict[str, HyperParams] = {}
    for i, spec in enumerate(layers):
        hp = spec.get("hyperparams")
        if hp:
            lname = spec.get("name", f"l{i}_{spec['type']}")
            per_unit[lname] = HyperParams(**hp) \
                if isinstance(hp, dict) else hp
    return OPTIMIZERS[kind](per_unit=per_unit, **kwargs)


class StandardWorkflow:
    """Convenience bundle: workflow + optimizer + decision settings from one
    config dict (the shape of a reference "workflow config" file)."""

    def __init__(self, config: dict):
        self.config = dict(config)
        layers = self.config["layers"]
        self.workflow = build_workflow(
            self.config.get("name", "StandardWorkflow"), layers,
            loss=self.config.get("loss", "softmax"),
            compute_dtype=self.config.get("compute_dtype"))
        okind = self.config.get("optimizer", "momentum")
        oargs = dict(self.config.get("optimizer_args", {}))
        self.optimizer = build_optimizer(okind, layers, **oargs)

    def make_trainer(self, loader, decision=None, snapshotter=None,
                     mesh=None, rule=None):
        from ..runtime import Decision, Trainer
        decision = decision or Decision(
            max_epochs=self.config.get("max_epochs"),
            fail_iterations=self.config.get("fail_iterations", 50))
        return Trainer(self.workflow, loader, self.optimizer, decision,
                       snapshotter, mesh=mesh, rule=rule,
                       pipeline_microbatches=self.config.get(
                           "pipeline_microbatches"),
                       pipeline_interleave=self.config.get(
                           "pipeline_interleave", 1))
