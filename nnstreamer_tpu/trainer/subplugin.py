"""Trainer sub-plugin API + the JAX/optax trainer (nns-learn).

Reference analog: the trainer sub-plugin vtable
(``nnstreamer_plugin_api_trainer.h``: create/destroy/start/stop/push_data/
getStatus) and its one implementation
``ext/nnstreamer/tensor_trainer/tensor_trainer_nntrainer.cc`` (SURVEY §2.8,
upstream-reconstructed).  The reference bridges to the external nntrainer C++
library; the TPU-native build trains with jitted optax steps instead.

TPU-first design (docs/TRAINING.md):

* **Device-resident state.**  Params and optimizer state live in HBM for
  the stage lifetime; the update step donates both, so steady-state
  training allocates nothing — the PR 10 aggregator-ring discipline.
* **Streaming window, not host accumulation.**  Samples append into a
  fixed ``[batch_size, ...]`` HBM window IN-PROGRAM
  (``dynamic_update_slice`` at a traced index — the device-aggregator
  ring's exact move) and a full window dispatches one update step; the
  host never holds an epoch of samples.  ``host-accumulate=true`` keeps
  the legacy stack-the-epoch path for A/B comparison.
* **Closed census.**  The stage compiles exactly
  :data:`TRAINER_PROGRAMS` programs for its lifetime — append, step,
  eval — with every shape static (a partial tail window steps through
  the SAME program via a masked loss with the live-count as a VALUE).
  ``jit._cache_size`` is pinned by tests and the deep lint prices the
  census via :func:`train_plan`, the same shared-arithmetic discipline
  as ``filters/llm.serving_plan``.
* **Mesh sharding.**  ``mesh=data:N`` (or ``data:N,model:M``) runs the
  step over an ICI mesh: the window's batch dim shards over ``data``
  (gradients all-reduced by GSPMD), params place per the zoo bundle's
  ``param_pspecs`` — model-axis leaves shard M ways, the rest replicate
  — so training scales exactly like serving (docs/BATCHING.md "2-D
  sharded dispatch").
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.log import logger, metrics
from ..core.registry import register_trainer

log = logger("trainer")

#: compiled programs one streaming JaxTrainer runs for its LIFETIME
#: (append, update step, validation eval) — the fixed-signature census
#: the deep lint prices (analysis/tracecheck.py) and nns-xray verifies
#: live (the llm serve loop's 3-program discipline)
TRAINER_PROGRAMS = 3


class TrainerError(RuntimeError):
    pass


class TrainerSubplugin:
    """Base class for tensor_trainer sub-plugins.

    Lifecycle (driven by the tensor_trainer element):
    ``open(props)`` → N× ``push_data(inputs, labels, is_validation)`` →
    ``train_epoch()`` per completed epoch → ``save(path)`` → ``close()``.
    """

    name: str = "base"

    def __init__(self):
        self.props: Dict[str, object] = {}

    def open(self, props: Dict[str, object]) -> None:
        # Keep the element's own (tracked) dict — see filters/base.py.
        self.props = props if isinstance(props, dict) else dict(props)

    def push_data(
        self, inputs: Sequence[np.ndarray], labels: Sequence[np.ndarray], is_validation: bool
    ) -> None:
        raise NotImplementedError

    def train_epoch(self) -> Dict[str, float]:
        """Consume the queued epoch of samples; returns stats:
        training_loss / training_accuracy / validation_loss /
        validation_accuracy (NaN where not applicable)."""
        raise NotImplementedError

    def save(self, path: str) -> str:
        raise NotImplementedError

    def load(self, path: str) -> None:
        raise NotImplementedError

    def queued(self) -> Tuple[int, int]:
        """(n_train, n_valid) samples awaiting train_epoch; the element uses
        this at EOS to decide whether a partial epoch remains."""
        return (0, 0)

    def close(self) -> None:
        pass


def _mlp_layer_shapes(layer_sizes: List[int]) -> List[Dict[str, tuple]]:
    """Shapes of :func:`_build_mlp`'s param tree without materializing it
    — the static pricing path (:func:`train_plan`) derives opt-state and
    gradient bytes from these via ``jax.eval_shape``."""
    return [
        {"w": (fan_in, fan_out), "b": (fan_out,)}
        for fan_in, fan_out in zip(layer_sizes[:-1], layer_sizes[1:])
    ]


def _build_mlp(layer_sizes: List[int], seed: int):
    """Tiny trainable MLP used when no zoo model is named.

    Returns (params, apply).  Kept deliberately simple — real models come
    from the zoo (models/mobilenet.py has init_params/param_pspecs).
    """
    rng = np.random.default_rng(seed)
    params = []
    for fan_in, fan_out in zip(layer_sizes[:-1], layer_sizes[1:]):
        scale = np.sqrt(2.0 / fan_in)
        params.append(
            {
                "w": (rng.standard_normal((fan_in, fan_out)) * scale).astype(np.float32),
                "b": np.zeros((fan_out,), np.float32),
            }
        )

    def apply(params, x):
        import jax.numpy as jnp

        h = x.reshape((x.shape[0], -1)).astype(jnp.float32)
        for i, layer in enumerate(params):
            h = h @ layer["w"] + layer["b"]
            if i < len(params) - 1:
                h = jnp.maximum(h, 0.0)
        return h

    return params, apply


def _make_optimizer(opt: str, lr: float):
    import optax

    if opt == "sgd":
        return optax.sgd(lr)
    if opt == "momentum":
        return optax.sgd(lr, momentum=0.9)
    return optax.adam(lr)


def _tree_nbytes(tree) -> int:
    """The ONE accounting walk (``filters/base.tree_param_bytes`` —
    nbytes when the leaf carries it, shape x itemsize for abstract
    leaves like eval_shape's ShapeDtypeStructs), so static pricing and
    the live ledger can never diverge arithmetically."""
    from ..filters.base import tree_param_bytes

    return tree_param_bytes(tree)


def train_plan(props: Dict[str, object]) -> Optional[Dict[str, object]]:
    """Static resource plan for one jax tensor_trainer stage — the ONE
    home for the arithmetic the deep lint prices "train state" with
    (analysis/tracecheck.py) and the runtime publishes to nns-xray, the
    ``filters/llm.serving_plan`` discipline.  Returns::

        {"param_bytes", "opt_bytes", "grad_bytes", "window_bytes",
         "programs", "batch_size", "pspecs", "params"}

    * ``opt_bytes`` — the optax state tree ABSTRACTED via
      ``jax.eval_shape(tx.init, params)``: no optimizer state ever
      materializes here;
    * ``grad_bytes`` — one gradient tree (== param bytes), transient per
      step (priced as activation-class HBM, not resident state);
    * ``window_bytes`` — the device-resident streaming sample window
      (``batch_size`` x (input + label bytes), label approximated as one
      int32 class id for ``softmax_ce`` when the stream's spec is not
      known statically);
    * ``pspecs`` / ``params`` — for the ``_pspec_audit`` model-axis walk
      (zoo bundles; ``None`` for the ad-hoc MLP).

    ``None`` when the model config cannot be resolved statically (the
    caller diagnoses ``training-unpriced``).  MLP params ARE materialized
    (a few KiB); zoo builds are the same test-scale bundles the deep
    pass already traces in ``_trace_node``.
    """
    model = str(props.get("model", props.get("model_config", "mlp:4:16:3")))
    bs = int(props.get("batch_size", props.get("batch-size", 16)))
    opt = str(props.get("optimizer", "adam"))
    lr = float(props.get("learning_rate", props.get("learning-rate", 1e-3)))
    import jax

    pspecs = None
    if model.startswith("mlp:"):
        try:
            sizes = [int(s) for s in model.split(":")[1:]]
        except ValueError:
            return None
        if len(sizes) < 2:
            return None
        params = [
            {"w": jax.ShapeDtypeStruct(s["w"], np.float32),
             "b": jax.ShapeDtypeStruct(s["b"], np.float32)}
            for s in _mlp_layer_shapes(sizes)
        ]
        in_bytes = sizes[0] * 4
        live_params = None
    else:
        from ..models import zoo

        try:
            opts = {k: str(v) for k, v in props.items()
                    if k in ("classes", "width", "size", "seed")}
            bundle = zoo.build(model, opts)
        except Exception:  # noqa: BLE001 - unpriceable, caller diagnoses
            return None
        live_params = bundle.params
        params = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
            if hasattr(a, "shape") and hasattr(a, "dtype") else a,
            bundle.params)
        pspecs = getattr(bundle, "param_pspecs", None)
        in_bytes = (int(bundle.in_spec.nbytes)
                    if bundle.in_spec is not None else 0)
    tx = _make_optimizer(opt, lr)
    try:
        opt_sds = jax.eval_shape(tx.init, params)
    except Exception:  # noqa: BLE001 - exotic trees: price params only
        opt_sds = None
    param_bytes = _tree_nbytes(params)
    label_bytes = 4  # one int32 class id (softmax_ce); mse streams vary
    if str(props.get("loss", "softmax_ce")) == "mse":
        label_bytes = in_bytes  # worst case: regression target ~ input
    return {
        "param_bytes": param_bytes,
        "opt_bytes": _tree_nbytes(opt_sds) if opt_sds is not None else 0,
        "grad_bytes": param_bytes,
        "window_bytes": bs * (in_bytes + label_bytes),
        "programs": TRAINER_PROGRAMS,
        "batch_size": bs,
        "pspecs": pspecs,
        "params": live_params,
    }


@register_trainer("jax")
class JaxTrainer(TrainerSubplugin):
    """Optax-based streaming trainer (see module docstring).

    Props (via tensor_trainer's ``framework-props`` / element props):

    * ``model`` — ``mlp:IN:HIDDEN:...:OUT`` or a zoo name (``mobilenet_v1``)
      whose builder accepts ``classes``/``width`` options;
    * ``optimizer`` — ``sgd`` | ``momentum`` | ``adam`` (default);
    * ``learning-rate`` — float, default 1e-3;
    * ``loss`` — ``softmax_ce`` (labels are int class ids or one-hot) |
      ``mse``;
    * ``batch-size`` — the streaming window width (default 16);
    * ``seed`` — param init seed;
    * ``mesh`` — ``data:N`` (batch sharded over N chips, grads
      all-reduced) or ``data:N,model:M`` (params additionally sharded
      per the bundle's ``param_pspecs``);
    * ``model-load-path`` — checkpoint to resume from (params, optimizer
      moments AND step counter restore — continuation is bit-identical);
    * ``host-accumulate`` — ``true`` keeps the legacy
      stack-the-whole-epoch host path (the bench A/B baseline).
    """

    name = "jax"

    def __init__(self):
        super().__init__()
        self._valid: List[Tuple[List[np.ndarray], List[np.ndarray]]] = []
        self._host_train: List[Tuple[List[np.ndarray], List[np.ndarray]]] = []
        self._lock = threading.Lock()
        self.params = None
        self.apply_fn: Optional[Callable] = None
        self.opt_state = None
        self._tx = None
        self._append_fn = None
        self._step_fn = None
        self._eval_fn = None
        self.step = 0
        self._mesh = None
        self._batch_sharding = None
        # streaming-window state (device arrays once the first sample's
        # shape is known)
        self._wx = None
        self._wy = None
        self._fill = 0  # samples in the window not yet stepped
        self._pending = 0  # samples pushed since the last train_epoch
        self._losses: List[float] = []
        self._accs: List[float] = []
        # nns-xray handoff (attach_xray): the three programs register
        # their compiles under "<stage>.learn"
        self._xray = None
        self._xray_stage = None
        self._xray_rec = None

    # -- lifecycle ---------------------------------------------------------
    def open(self, props: Dict[str, object]) -> None:
        super().open(props)

        model = str(props.get("model", "mlp:4:16:3"))
        seed = int(props.get("seed", 0))
        self._pspecs = None
        if model.startswith("mlp:"):
            sizes = [int(s) for s in model.split(":")[1:]]
            self.params, self.apply_fn = _build_mlp(sizes, seed)
        else:
            from ..models import zoo

            opts = {
                k: str(v)
                for k, v in props.items()
                if k in ("classes", "width", "size", "seed")
            }
            bundle = zoo.build(model, opts)
            self.params, self.apply_fn = bundle.params, bundle.apply_fn
            self._pspecs = getattr(bundle, "param_pspecs", None)

        lr = float(props.get("learning_rate", props.get("learning-rate", 1e-3)))
        opt = str(props.get("optimizer", "adam"))
        self._tx = _make_optimizer(opt, lr)

        self.loss_kind = str(props.get("loss", "softmax_ce"))
        self.batch_size = int(props.get("batch_size", props.get("batch-size", 16)))
        self.host_accumulate = str(
            props.get("host_accumulate", props.get("host-accumulate", "false"))
        ).lower() in ("true", "1", "yes")

        mesh_prop = str(props.get("mesh", "") or "")
        if mesh_prop:
            self._setup_mesh(mesh_prop)

        # A checkpoint's opt_state (Adam moments etc.) wins over a fresh
        # init; under a mesh the fresh init happens AFTER placement
        # (inside _place_on_mesh) so moments inherit each placed leaf's
        # sharding and a full-size pre-placement tree is never built
        # just to be discarded.
        load = props.get("model_load_path") or props.get("model-load-path")
        if load:
            self.load(str(load))
        if self._mesh is not None:
            self._place_on_mesh()
        else:
            if self.opt_state is None:
                self.opt_state = self._tx.init(self.params)
            self._commit_to_device()

    def _commit_to_device(self) -> None:
        """Commit params + opt state to device arrays UP FRONT (the llm
        serve loop's carried-state discipline): jit's fast path keys on
        argument TYPE, so a first step fed host numpy leaves would mint
        a second cache entry and break the 3-program census pin."""
        import jax
        import jax.numpy as jnp

        as_dev = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: jnp.asarray(a) if hasattr(a, "shape") else a, t)
        self.params = as_dev(self.params)
        if self.opt_state is not None:
            self.opt_state = as_dev(self.opt_state)

    def _setup_mesh(self, spec: str) -> None:
        """``data:N`` / ``data:N,model:M`` — the same (data, model) axes
        the serving pipeline places on (pipeline/plan.mesh_plan)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel import make_mesh

        axes = {"data": 0, "model": 1}
        sizes = {"data": 1, "model": 1}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            name, _, n = part.partition(":")
            name = name.strip() or "data"
            if name not in axes:
                raise TrainerError(
                    f"bad mesh spec {spec!r}: axis {name!r} (valid: "
                    "data, model)")
            sizes[name] = int(n) if n else len(jax.devices())
        need = sizes["data"] * sizes["model"]
        if len(jax.devices()) < need:
            raise TrainerError(
                f"mesh {spec!r} needs {need} devices, have "
                f"{len(jax.devices())}")
        kw = {"data": sizes["data"]}
        if sizes["model"] > 1:
            kw["model"] = sizes["model"]
        self._mesh = make_mesh(devices=jax.devices()[:need], **kw)
        self._batch_sharding = NamedSharding(self._mesh, P("data"))

    def _place_on_mesh(self) -> None:
        """Params + opt state onto the mesh: leaves whose ``param_pspecs``
        name the ``model`` axis shard over it, everything else replicates
        (``parallel/sharding.shard_params`` — the exact placement
        ``Element.place_params`` runs for serving stages).  The opt state
        is re-initialized FROM the placed params so Adam moments inherit
        each leaf's sharding; a checkpoint-resumed opt state is placed
        leaf-by-leaf alongside instead."""
        from ..parallel.mesh import mesh_axis_size
        from ..parallel.sharding import replicate, shard_params

        old_opt = self.opt_state  # non-None only when a checkpoint loaded
        if mesh_axis_size(self._mesh, "model") > 1 and self._pspecs is not None:
            from ..parallel.sharding import placement_split

            n_shard, n_rep = placement_split(self.params, self._pspecs)
            self.params = shard_params(self._mesh, self.params, self._pspecs)
            # shard-vs-replica split: proof of model-axis placement, the
            # serving stages' counter convention (elements/filter.py)
            metrics.count("trainer.param_shards", n_shard)
            metrics.count("trainer.param_replicas", n_rep)
        else:
            self.params = replicate(self._mesh, self.params)
            metrics.count("trainer.param_replications")
        if old_opt is not None:
            # a checkpoint-resumed opt state replicates onto the mesh:
            # its tree shape does not pair with param pspecs (optax
            # nests params-shaped trees inside namedtuples), and
            # replicated moments are always CORRECT — GSPMD re-shards
            # them through the step's output shardings if beneficial
            self.opt_state = replicate(self._mesh, old_opt)
        else:
            # commit EVERY opt leaf to the mesh up front (the llm serve
            # loop's carried-state discipline): zeros_like inherits the
            # param leaf's placement, but optax's step counter is a
            # fresh uncommitted scalar — after the first step it comes
            # back mesh-committed, and that sharding flip would mint a
            # second step signature (census drift)
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(self._mesh, P())
            self.opt_state = jax.tree_util.tree_map(
                lambda a: (a if getattr(a, "committed", False)
                           else jax.device_put(a, rep))
                if hasattr(a, "shape") else a,
                self._tx.init(self.params))

    # -- nns-xray ----------------------------------------------------------
    def attach_xray(self, registry, stage: str, rec=None) -> None:
        """Install the predicted census (append/step/eval = one compile
        each — :data:`TRAINER_PROGRAMS`) and track the jitted programs
        under ``<stage>.learn``; idempotent, the ``Framework.attach_xray``
        contract."""
        self._xray = registry
        self._xray_stage = f"{stage}.learn"
        self._xray_rec = rec
        registry.expect(self._xray_stage, "append", budget=1,
                        note="train_plan streaming-window append")
        registry.expect(self._xray_stage, "step", budget=1,
                        note="train_plan fixed update-step signature")
        registry.expect(self._xray_stage, "eval", budget=1,
                        note="train_plan validation eval")
        self._wrap_xray()

    def _wrap_xray(self) -> None:
        xr = self._xray
        if xr is None:
            return
        if self._append_fn is not None:
            self._append_fn = xr.track(self._append_fn, self._xray_stage,
                                       "append", rec=self._xray_rec)
        if self._step_fn is not None:
            self._step_fn = xr.track(self._step_fn, self._xray_stage,
                                     "step", rec=self._xray_rec)
        if self._eval_fn is not None:
            self._eval_fn = xr.track(self._eval_fn, self._xray_stage,
                                     "eval", rec=self._xray_rec)

    # -- data --------------------------------------------------------------
    def push_data(self, inputs, labels, is_validation: bool) -> None:
        if len(inputs) != 1 or len(labels) != 1:
            # Silently training on inputs[0] would corrupt multi-input runs.
            raise TrainerError(
                f"{self.name} trains single-input/single-label models; got "
                f"{len(inputs)} inputs, {len(labels)} labels"
            )
        sample = ([np.asarray(t) for t in inputs], [np.asarray(t) for t in labels])
        if is_validation:
            with self._lock:
                self._valid.append(sample)
            return
        if self.host_accumulate:
            with self._lock:
                self._host_train.append(sample)
                self._pending += 1
            return
        with self._lock:
            self._append_sample(sample[0][0], sample[1][0])
            self._pending += 1
            if self._fill >= self.batch_size:
                self._dispatch_step(self._fill)
                self._fill = 0

    def queued(self) -> Tuple[int, int]:
        """Samples not yet consumed by a ``train_epoch`` (streamed samples
        already stepped still count: their epoch stats await collection)."""
        with self._lock:
            return self._pending, len(self._valid)

    # -- device window -----------------------------------------------------
    def _ensure_window(self, x: np.ndarray, y: np.ndarray) -> None:
        if self._wx is not None:
            return
        import jax.numpy as jnp

        bs = max(1, self.batch_size)
        # label window keeps the per-sample shape; the trailing-singleton
        # collapse happens inside the step's loss math
        self._wx = jnp.zeros((bs,) + tuple(x.shape), jnp.asarray(x).dtype)
        self._wy = jnp.zeros((bs,) + tuple(y.shape), jnp.asarray(y).dtype)
        if self._mesh is not None:
            # mesh-committed like params/opt: the step's donated outputs
            # come back committed, and an uncommitted first-call window
            # would flip the arg sharding and mint a second signature
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(self._mesh, P())
            self._wx = jax.device_put(self._wx, rep)
            self._wy = jax.device_put(self._wy, rep)
        self._build_programs()

    def _append_sample(self, x: np.ndarray, y: np.ndarray) -> None:
        self._ensure_window(x, y)
        # np.int32 index CONSISTENTLY: mixing python ints in would mint a
        # weak-typed second signature (the census-drift trap nns-xray
        # catches — utils/xray.abstract_signature)
        self._wx, self._wy = self._append_fn(
            self._wx, self._wy, np.int32(self._fill), np.asarray(x),
            np.asarray(y))
        self._fill += 1

    def _dispatch_step(self, count: int) -> None:
        """One fixed-shape update step over the window's first ``count``
        rows (masked loss — a partial tail window reuses the SAME
        compiled program; ``count`` is a VALUE, never a shape)."""
        self.params, self.opt_state, loss, acc = self._step_fn(
            self.params, self.opt_state, self._wx, self._wy,
            np.int32(count))
        self._losses.append(float(loss))
        self._accs.append(float(acc))
        self.step += 1

    # -- math --------------------------------------------------------------
    def _per_example_loss(self, params, x, y):
        """Per-row (loss, correct) — shared by the masked step and the
        validation eval so both paths compute the same math."""
        import jax
        import jax.numpy as jnp

        logits = self.apply_fn(params, x)
        if isinstance(logits, (tuple, list)):
            logits = logits[0]
        if self.loss_kind == "mse":
            per = jnp.mean(
                (logits - y.reshape(logits.shape)) ** 2,
                axis=tuple(range(1, logits.ndim)))
            correct = jnp.full(per.shape, jnp.nan, per.dtype)
        else:
            if y.ndim >= 2 and y.shape[-1] == logits.shape[-1]:
                labels = jnp.argmax(y.reshape((y.shape[0], -1)), axis=-1)
            else:
                labels = y.reshape((y.shape[0],)).astype(jnp.int32)
            logp = jax.nn.log_softmax(logits)
            per = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
            correct = (jnp.argmax(logits, axis=-1) == labels).astype(
                jnp.float32)
        return per, correct

    def _masked_stats(self, params, x, y, count):
        import jax.numpy as jnp

        per, correct = self._per_example_loss(params, x, y)
        mask = (jnp.arange(per.shape[0]) < count).astype(per.dtype)
        cf = count.astype(per.dtype) if hasattr(count, "astype") \
            else jnp.asarray(count, per.dtype)
        loss = jnp.sum(per * mask) / cf
        acc = jnp.sum(correct * mask) / cf
        return loss, acc

    def _build_programs(self) -> None:
        import jax
        from jax import lax

        # donation reuses the window/params/opt HBM in place — steady-
        # state training allocates nothing.  CPU backends can't donate
        # and would warn per compile (the FusedElement gate).
        donate = jax.default_backend() not in ("cpu",)

        win_sh = None
        if self._mesh is not None:
            # the step's output-pinning rule applies to append too: the
            # donated window must come back with its INPUT sharding, or
            # the second call's flipped arg sharding mints a phantom
            # append signature (census drift)
            win_sh = getattr(self._wx, "sharding", None)

        def append(wx, wy, i, x, y):
            wx = lax.dynamic_update_slice(
                wx, x[None].astype(wx.dtype), (i,) + (0,) * (wx.ndim - 1))
            wy = lax.dynamic_update_slice(
                wy, y[None].astype(wy.dtype), (i,) + (0,) * (wy.ndim - 1))
            if win_sh is not None:
                wx = lax.with_sharding_constraint(wx, win_sh)
                wy = lax.with_sharding_constraint(wy, win_sh)
            return wx, wy

        self._append_fn = jax.jit(
            append, donate_argnums=(0, 1) if donate else ())

        constrain = self._batch_sharding
        pin_p = pin_o = None
        if self._mesh is not None:
            # pin the step's donated outputs to the INPUT placement: a
            # model-sharded leaf whose output sharding GSPMD re-decided
            # would flip the next call's arg shardings and mint a second
            # step signature (census drift)
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(self._mesh, P())
            shs = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda a: getattr(a, "sharding", None) or rep, t)
            pin_p, pin_o = shs(self.params), shs(self.opt_state)

        def _pin(tree, shardings):
            if shardings is None:
                return tree
            return jax.tree_util.tree_map(
                lambda t, s: lax.with_sharding_constraint(t, s),
                tree, shardings)

        def step(params, opt_state, wx, wy, count):
            if constrain is not None:
                wx = lax.with_sharding_constraint(wx, constrain)

            def loss_fn(p):
                return self._masked_stats(p, wx, wy, count)

            (loss, acc), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, opt_state = self._tx.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(
                lambda p, u: p + u, params, updates)
            return _pin(params, pin_p), _pin(opt_state, pin_o), loss, acc

        self._step_fn = jax.jit(
            step, donate_argnums=(0, 1) if donate else ())

        def evaluate(params, x, y, count):
            # the step's masked math over the step's [batch-size] window
            # shape: validation runs in window-sized chunks, so the eval
            # signature is FIXED regardless of the validation-set size
            # (a varying set — e.g. the partial epoch flushed at EOS —
            # must not mint a second program and fire false drift)
            return self._masked_stats(params, x, y, count)

        self._eval_fn = jax.jit(evaluate)
        self._wrap_xray()

    def compile_counts(self) -> Dict[str, int]:
        """Live ``jit._cache_size`` per program — the census pin tests
        read (append/step/eval must each stay at 1 across epoch churn)."""
        out = {}
        for kind, fn in (("append", self._append_fn),
                         ("step", self._step_fn),
                         ("eval", self._eval_fn)):
            if fn is None:
                continue
            try:
                out[kind] = int(fn._cache_size())
            except Exception:  # noqa: BLE001 - non-jit wrapper
                out[kind] = -1
        return out

    # -- epochs ------------------------------------------------------------
    def train_epoch(self) -> Dict[str, float]:
        with self._lock:
            if self.host_accumulate:
                train, self._host_train = self._host_train, []
                if not train:
                    raise TrainerError(
                        "train_epoch called with no queued samples")
                self._train_host(train)
            else:
                if self._pending == 0:
                    raise TrainerError(
                        "train_epoch called with no queued samples")
                if self._fill:
                    # partial tail window: masked step through the SAME
                    # program — count is a value, the census stays closed
                    self._dispatch_step(self._fill)
                    self._fill = 0
            losses, self._losses = self._losses, []
            accs, self._accs = self._accs, []
            valid, self._valid = self._valid, []
            self._pending = 0

        stats = {
            "training_loss": float(np.mean(losses)) if losses else float("nan"),
            "training_accuracy": float(np.mean(accs)) if accs else float("nan"),
            "validation_loss": float("nan"),
            "validation_accuracy": float("nan"),
        }
        if valid:
            if self._eval_fn is None:
                self._ensure_window(valid[0][0][0], valid[0][1][0])
            import jax.numpy as jnp

            bs = max(1, self.batch_size)
            tot_l = tot_a = 0.0
            for off in range(0, len(valid), bs):
                chunk = valid[off:off + bs]
                x = np.stack([s[0][0] for s in chunk])
                y = np.stack([s[1][0] for s in chunk])
                n = x.shape[0]
                if n < bs:  # pad to the window shape; the mask hides it
                    x = np.concatenate(
                        [x, np.zeros((bs - n,) + x.shape[1:], x.dtype)])
                    y = np.concatenate(
                        [y, np.zeros((bs - n,) + y.shape[1:], y.dtype)])
                vl, va = self._eval_fn(self.params, jnp.asarray(x),
                                       jnp.asarray(y), np.int32(n))
                tot_l += float(vl) * n
                tot_a += float(va) * n
            stats["validation_loss"] = tot_l / len(valid)
            stats["validation_accuracy"] = tot_a / len(valid)
        log.debug("epoch stats %s", stats)
        return stats

    def _train_host(self, train) -> None:
        """Legacy host-accumulated epoch (``host-accumulate=true``): the
        whole epoch stacks on host, minibatches slice from the stack.
        Kept as the A/B baseline of the streaming window; the
        step program is SHARED with the streaming path (same masked
        signature), so the census stays closed either way."""
        bs = max(1, self.batch_size)
        self._ensure_window(train[0][0][0], train[0][1][0])
        import jax.numpy as jnp

        for off in range(0, len(train), bs):
            chunk = train[off:off + bs]
            x = np.stack([s[0][0] for s in chunk])
            y = np.stack([s[1][0] for s in chunk])
            n = x.shape[0]
            if n < bs:  # pad to the window shape; the mask hides the pad
                x = np.concatenate(
                    [x, np.zeros((bs - n,) + x.shape[1:], x.dtype)])
                y = np.concatenate(
                    [y, np.zeros((bs - n,) + y.shape[1:], y.dtype)])
            self.params, self.opt_state, loss, acc = self._step_fn(
                self.params, self.opt_state, jnp.asarray(x),
                jnp.asarray(y), np.int32(n))
            self._losses.append(float(loss))
            self._accs.append(float(acc))
            self.step += 1

    # -- live accounting (nns-xray HBM ledger) ------------------------------
    def param_nbytes(self) -> int:
        return _tree_nbytes(self.params) if self.params is not None else 0

    def train_state_bytes(self) -> int:
        """Device-resident training state: optimizer moments + the
        streaming sample window — the bytes the ledger's ``train_state``
        category reconciles against :func:`train_plan` (gradients are
        transient per step and priced as activations)."""
        total = _tree_nbytes(self.opt_state) if self.opt_state is not None \
            else 0
        for w in (self._wx, self._wy):
            if w is not None:
                total += int(getattr(w, "nbytes", 0) or 0)
        return total

    def export_params(self):
        """The CURRENT param tree (device arrays) — what
        ``Pipeline.swap_params`` moves into a serving stage.  The serve
        side device_puts per its own placement, so handing live arrays
        is safe (the swap never mutates them)."""
        return self.params

    # -- persistence -------------------------------------------------------
    def save(self, path: str) -> str:
        from .checkpoint import save_checkpoint

        got = save_checkpoint(path, self.params, self.opt_state, self.step,
                              fsync=True)
        metrics.count("trainer.ckpt_writes")
        return got

    def load(self, path: str) -> None:
        from .checkpoint import load_checkpoint

        self.params, opt_state, self.step = load_checkpoint(path)
        if opt_state is not None:
            self.opt_state = opt_state
