"""The work a step needs, from its shapes alone: the yardstick of every
roofline and utilization metric.

- Operations are 2*M*K*N per GEMM, however many MXU passes an implementation
  makes; elementwise work is not counted.
- Bytes are the operands at the least storage the requested mode allows:
  weights at 2 bytes per bf16 limb the mode needs, capped at the 4 bytes of
  f32, plus the live KV cache or SSM state that the step reads and writes.

So the shares read the same work whatever implements it, and no
implementation on bf16 storage and the bf16 peak can push them past 100%.
A change that moves GEMMs to the int8 rate needs the peak restated first.

A model is a list of layers, each a tuple of components (``work/<name>.py``,
found by name); a layer's work is the sum of its components', the model's
the sum of its layers' and the vocabulary head once.  The list is the
configuration file's ``layers``, one list of component names per layer::

    "layers": [["ssm", "mlp"], ["ssm", "mlp"], ["attention", "mlp"]]

or, without that key, the file's ``family`` (``FAMILY_LAYER``, the one
layer it repeats).  ``Dims.of`` holds the list against the served model.
"""
from __future__ import annotations

import collections
import dataclasses
import importlib

#: bf16 limbs each f32-ladder mode splits an operand into
LIMBS = {"M8": 1, "M16": 2, "M24": 3}
#: the layer a family repeats, where the configuration file lists no layers
FAMILY_LAYER = {"dense": ("attention", "mlp"), "ssm": ("ssm",)}


def weight_bytes_per_elem(mode: str) -> int:
    if mode not in LIMBS:
        raise ValueError(f"no storage rule for mode {mode!r}")
    return min(2 * LIMBS[mode], 4)


def gemm_flops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def component(name: str):
    """The module ``chipbench.work.<name>`` that counts a component's work."""
    if not name.isidentifier():
        raise ValueError(f"no work count for component {name!r}: not a module name")
    try:
        return importlib.import_module(f"chipbench.work.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"chipbench.work.{name}":
            raise
        raise ValueError(f"no work count for component {name!r}: "
                         f"no chipbench/work/{name}.py") from None


def layer_list(config: dict, n_layers: int) -> tuple:
    """Each layer's components, from a configuration file's ``layers`` or,
    without it, its ``family`` (module docstring)."""
    if "layers" in config:
        return tuple(tuple(layer) for layer in config["layers"])
    family = config.get("family")
    if family not in FAMILY_LAYER:
        raise ValueError(f"no layer list for family {family!r}: "
                         "give the configuration file a 'layers' key")
    return (FAMILY_LAYER[family],) * n_layers


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the work depends on (the program's ArchConfig fields) and
    the components of each layer (``layer_list``)."""

    n_layers: int
    d_model: int
    vocab: int
    layers: tuple
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    ssm_state: int = 0
    ssm_head_dim: int = 0
    ssm_expand: int = 0
    ssm_conv: int = 0
    kv_cache_dtype: str = "bfloat16"

    def __post_init__(self):
        if len(self.layers) != self.n_layers:
            raise ValueError(f"{len(self.layers)} layers listed for a model of {self.n_layers}")
        for name in {c for layer in self.layers for c in layer}:
            component(name)

    @classmethod
    def of(cls, cfg, config: dict, kinds, gemm_shapes) -> "Dims":
        """The widths of the program's ArchConfig ``cfg`` and the layers of
        the cell's configuration file ``config``, held against the served
        model: layers of one kind in the program's ``kinds`` (one per
        layer) list the same components, layers of two kinds different
        ones, and the layers' GEMMs have the (K, N) shapes of its weights
        (``gemm_shapes``, ``chipbench.weights.gemm_shapes``: every ``w``
        leaf of its parameter tree but the embedding and the vocabulary
        head, wherever the tree keeps its layers)."""
        fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cls)
                  if f.name != "layers"}
        d = cls(**fields, layers=layer_list(config, cfg.n_layers))
        kinds = list(kinds)
        pairs = set(zip(kinds, d.layers))
        if len(kinds) != d.n_layers or not (
                len(pairs) == len(set(kinds)) == len(set(d.layers))):
            raise ValueError(f"the listed layers do not follow the program's kinds: "
                             f"{sorted(pairs)} over {len(kinds)} layers")
        listed = {s for layer in d.layers for name in layer
                  for shapes in component(name).gemms(d).values() for s in shapes}
        if listed != set(gemm_shapes):
            raise ValueError(f"the layers' GEMMs {sorted(listed)} are not the served "
                             f"model's weights {sorted(gemm_shapes)}")
        return d

    def _total(self, fn: str, *args) -> int:
        """``fn`` of every component summed over the layers, 0 where a
        component has no such function."""
        counts = collections.Counter(c for layer in self.layers for c in layer)
        total = 0
        for name, n in counts.items():
            f = getattr(component(name), fn, None)
            if f is not None:
                total += n * f(self, *args)
        return total

    # -- weights per op class: (K, N) of each GEMM a token goes through ------

    def gemms(self) -> dict[str, list[tuple[int, int]]]:
        """Per op class, the (K, N) of every GEMM of one token's forward
        pass, all layers and the vocabulary head included."""
        out: dict[str, list[tuple[int, int]]] = {}
        for layer in self.layers:
            for name in layer:
                for op, shapes in component(name).gemms(self).items():
                    out.setdefault(op, []).extend(shapes)
        out.setdefault("logits", []).append((self.d_model, self.vocab))
        return out

    def matmul_params(self) -> int:
        return sum(k * n for shapes in self.gemms().values() for k, n in shapes)

    def weight_bytes(self, mode_for) -> int:
        """Bytes of every GEMM weight at the storage its op's mode allows;
        ``mode_for(op) -> "M8" | "M16" | "M24"``."""
        return sum(k * n * weight_bytes_per_elem(mode_for(op))
                   for op, shapes in self.gemms().items() for k, n in shapes)

    # -- per-row state ----------------------------------------------------------

    def kv_bytes_per_token(self) -> int:
        """KV cache bytes one cached token holds over all layers."""
        return self._total("token_bytes")

    def ssm_state_bytes(self) -> int:
        """State bytes one sequence holds over all layers, whatever its
        length (the SSM state and conv window)."""
        return self._total("state_bytes")

    # -- one step ---------------------------------------------------------------

    def decode_flops(self, contexts: list[int]) -> int:
        """One decode token for each row, row ``i`` attending over
        ``contexts[i]`` cached tokens (its own included)."""
        return (2 * len(contexts) * self.matmul_params()
                + sum(self._total("decode_flops", c) for c in contexts))

    def prefill_flops(self, prompt_len: int) -> int:
        """A causal forward pass over one prompt, with the logits of its
        last position only: the one a request needs."""
        head = self.d_model * self.vocab
        return (2 * prompt_len * (self.matmul_params() - head) + 2 * head
                + self._total("prefill_flops", prompt_len))

    def decode_bytes(self, contexts: list[int], mode_for) -> int:
        """Least bytes of one decode step: the weights once, plus what each
        live row's components read and write (its cache, its state)."""
        return self.weight_bytes(mode_for) + sum(self._total("decode_bytes", c)
                                                 for c in contexts)


def least_seconds(flops: float, nbytes: float, peaks) -> tuple[float, str]:
    """Roofline bound of some work on a chip: the larger of the compute and
    the memory time, and which of the two bounds it."""
    tc, tm = flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")
