"""Cellpose fine-tuning on TPU — training sessions, live inference, export.

The reference (ref apps/cellpose-finetuning/main.py, 5211 LoC) fine-tunes
Cellpose-SAM on exactly one GPU through a re-implemented torch train loop
with callbacks, a stop-file check, per-epoch snapshots feeding live
inference, and a ``status.json`` session protocol polled by the browser
frontend (:1740-1900, :1278-1360, :3682-4966). This TPU rebuild keeps the
session protocol — session dirs, ``status.json``, STOP file, per-epoch
snapshots, restart-from-snapshot — and replaces the compute:

- ``CellposeNet`` (bioengine_tpu/models/cellpose.py), a JAX/optax train
  step jitted **data-parallel over every local chip** via
  ``jit_data_parallel_step`` — gradients all-reduce over ICI, a
  capability the reference does not have (SURVEY.md §2.3).
- Training targets (flow fields) from instance masks via
  ``ops.flows.masks_to_flows`` on host, once per session.
- Snapshots are flat-npz ``jax_params`` — the exact weight format the
  model-runner app serves, so ``export_model`` emits a ready-to-serve
  BioImage-Model-Zoo-style package.
"""

import asyncio
import contextlib
import json
import shutil
import time
import uuid
from pathlib import Path
from typing import Optional

import numpy as np
import yaml

from bioengine_tpu.rpc import schema_method

# session states with no train thread behind them anymore
_TERMINAL_STATES = ("completed", "failed", "stopped", "interrupted")

DEFAULT_CONFIG = {
    # "unet" = CellposeNet (residual U-Net); "sam" = CellposeSAM, the
    # transformer-backbone family member (models/cellpose_sam.py);
    # "cpsam" = models/sam.CpSAM, the faithful pretrained Cellpose-SAM
    # architecture (SAM ViT encoder + readout) — set "pretrained_path"
    # to a converted checkpoint (runtime.convert.convert_checkpoint /
    # `bioengine models convert --arch cpsam`) to fine-tune from the
    # foundation weights like the reference does
    # (ref apps/cellpose-finetuning/main.py:2248, model_type="cpsam");
    # "stardist" = models/stardist.StarDist2D, star-convex polygons
    # (prob + ray-distance heads) instead of flow fields — a capability
    # the reference app does not have (it is cellpose-only)
    "backbone": "unet",
    "features": [32, 64, 128, 256],      # unet/stardist backbones
    "patch_size": 8,                      # sam/cpsam backbones
    "dim": 256,
    "depth": 8,
    "num_heads": 8,
    "n_rays": 32,                         # stardist backbone (even)
    "max_dist": 64,                       # stardist ray-length cap (px):
    #   raise it when instances exceed ~64 px radius or ray targets (and
    #   therefore predicted polygons) truncate at the cap
    "pretrained_path": None,              # flat-npz jax_params to start from
    "learning_rate": 1e-4,
    "weight_decay": 1e-5,
    "epochs": 10,
    "batch_size": 8,
    "tile": 128,
    "seed": 0,
}

# cpsam-only architecture knobs, overridable in config; the defaults in
# models/sam.py are the ViT-L checkpoint shape
_CPSAM_KEYS = (
    "window_size", "global_attn_indexes", "neck_dim", "pretrain_grid",
    "mlp_ratio",
)


# the pretrained cpsam checkpoint shape (ViT-L @ patch 8). When the
# user selects backbone "cpsam" these beat DEFAULT_CONFIG's small
# unet/sam sizes — otherwise the documented minimal config
# {"backbone": "cpsam", "pretrained_path": ...} would silently build a
# dim-256/depth-8 model and reject every real checkpoint.
_CPSAM_ARCH_DEFAULTS = {
    "patch_size": 8, "dim": 1024, "depth": 24, "num_heads": 16,
    "tile": 256,
}


def _merge_config(config: Optional[dict]) -> dict:
    config = dict(config or {})
    cfg = {**DEFAULT_CONFIG, **config}
    if cfg.get("backbone") == "cpsam":
        for k, v in _CPSAM_ARCH_DEFAULTS.items():
            if k not in config:
                cfg[k] = v
    if cfg.get("backbone") == "stardist":
        n_rays = float(cfg["n_rays"])
        if not n_rays.is_integer() or n_rays < 2 or int(n_rays) % 2:
            # reject HERE, synchronously in start_training — target
            # derivation is the expensive step and must not run for a
            # config the train loop would refuse anyway (and int()
            # truncation must not silently accept 8.9 as 8)
            raise ValueError(
                f"n_rays must be an even integer >= 2, got {cfg['n_rays']}"
            )
        cfg["n_rays"] = int(n_rays)
    return cfg


def _model_channels(cfg: dict) -> int:
    """cpsam is a 3-channel model (its pretrained patch embedding is
    3-channel); the app's prepared batches are [cyto, nucleus] and get
    a zero third channel at the model boundary."""
    return 3 if cfg.get("backbone") == "cpsam" else 2


def _to_model_channels(x: np.ndarray, cfg: dict) -> np.ndarray:
    c = _model_channels(cfg)
    if x.shape[-1] == c:
        return x
    pad = np.zeros((*x.shape[:-1], c - x.shape[-1]), x.dtype)
    return np.concatenate([x, pad], axis=-1)


def _flat_shapes(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flat_shapes(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = tuple(v.shape)
    return out


def _check_pretrained_tree(params: dict, expect: dict) -> None:
    """Loud structural validation of a pretrained checkpoint against the
    configured architecture: missing/unexpected leaves and shape
    mismatches name themselves instead of failing deep inside jit.
    Position/rel-pos tables are declared at their checkpoint extent
    (``pretrain_grid``/``window_size`` config) and resized at apply, so
    exact shape equality is the correct check for every leaf."""
    got, want = _flat_shapes(params), _flat_shapes(expect)
    missing = sorted(set(want) - set(got))
    extra = sorted(set(got) - set(want))
    bad = [
        f"{k}: checkpoint {got[k]} vs model {want[k]}"
        for k in sorted(set(got) & set(want))
        if got[k] != want[k]
    ]
    if missing or extra or bad:
        raise ValueError(
            "pretrained_path does not match the configured architecture: "
            f"missing={missing[:5]} unexpected={extra[:5]} "
            f"shape_mismatch={bad[:5]}"
        )


def build_model(cfg: dict):
    """(model, divisor) for the configured backbone.

    The cellpose family (unet/sam/cpsam) shares one output contract —
    (B, H, W, 3) flow/cellprob logits — so its train step, loss, and
    flow postprocessing are backbone-agnostic. The stardist backbone
    emits (B, H, W, 1 + n_rays) prob/ray logits instead: adding a
    backbone with its own output contract means wiring ALL of
    _prepare_training_data (targets), _train_loop (step + aug),
    _infer (postprocessing), and infer_3d (support or reject), the way
    the stardist branches in each of those do."""
    backbone = cfg.get("backbone", "unet")
    if backbone == "cpsam":
        from bioengine_tpu.models.sam import CpSAM

        kw = {k: cfg[k] for k in _CPSAM_KEYS if k in cfg}
        if "global_attn_indexes" in kw:
            kw["global_attn_indexes"] = tuple(kw["global_attn_indexes"])
        model = CpSAM(
            patch_size=int(cfg.get("patch_size", 8)),
            dim=int(cfg.get("dim", 1024)),
            depth=int(cfg.get("depth", 24)),
            num_heads=int(cfg.get("num_heads", 16)),
            **kw,
        )
        return model, model.divisor
    if backbone == "sam":
        from bioengine_tpu.models.cellpose_sam import CellposeSAM

        model = CellposeSAM(
            patch_size=int(cfg.get("patch_size", 8)),
            dim=int(cfg.get("dim", 256)),
            depth=int(cfg.get("depth", 8)),
            num_heads=int(cfg.get("num_heads", 8)),
            in_channels=2,
        )
        return model, model.divisor
    if backbone == "stardist":
        from bioengine_tpu.models.stardist import StarDist2D

        # always merged by _merge_config (which also rejects odd counts
        # — the horizontal-flip augmentation permutes ray indices by
        # (n_rays/2 - r) mod n_rays, only a bijection for even counts)
        model = StarDist2D(
            n_rays=int(cfg["n_rays"]), features=tuple(cfg["features"]),
            in_channels=2,
        )
        return model, model.divisor
    from bioengine_tpu.models.cellpose import CellposeNet

    model = CellposeNet(features=tuple(cfg["features"]), in_channels=2)
    return model, 2 ** (len(cfg["features"]) - 1)


def _arch_entry(cfg: dict) -> dict:
    """rdf.yaml architecture stanza for the configured backbone — the
    registry name + kwargs the model-runner uses to rebuild it."""
    backbone = cfg.get("backbone", "unet")
    if backbone == "cpsam":
        kw = {
            "patch_size": int(cfg.get("patch_size", 8)),
            "dim": int(cfg.get("dim", 1024)),
            "depth": int(cfg.get("depth", 24)),
            "num_heads": int(cfg.get("num_heads", 16)),
        }
        for k in _CPSAM_KEYS:
            if k in cfg:
                kw[k] = (
                    list(cfg[k]) if k == "global_attn_indexes" else cfg[k]
                )
        return {"name": "cpsam", "kwargs": kw}
    if backbone == "sam":
        return {
            "name": "cellpose-sam",
            "kwargs": {
                "patch_size": int(cfg.get("patch_size", 8)),
                "dim": int(cfg.get("dim", 256)),
                "depth": int(cfg.get("depth", 8)),
                "num_heads": int(cfg.get("num_heads", 8)),
                "in_channels": 2,
            },
        }
    if backbone == "stardist":
        return {
            "name": "stardist2d",
            "kwargs": {
                "n_rays": int(cfg["n_rays"]),
                "features": list(cfg["features"]),
                "in_channels": 2,
            },
        }
    return {
        "name": "cellpose",
        "kwargs": {"features": list(cfg["features"]), "in_channels": 2},
    }


def _now() -> float:
    return time.time()


class TrainingSession:
    """One fine-tune run: a directory with status.json, snapshots, STOP."""

    def __init__(self, root: Path, session_id: str, config: dict):
        self.session_id = session_id
        self.dir = root / session_id
        self.models_dir = self.dir / "models"
        self.data_dir = self.dir / "data"
        self.models_dir.mkdir(parents=True, exist_ok=True)
        self.data_dir.mkdir(parents=True, exist_ok=True)
        self.config = config
        self.task: asyncio.Task | None = None
        # True while start_training is still writing this session's data
        self.preparing = False

    # ---- status.json protocol (ref main.py:1740-1900) --------------------

    @property
    def status_path(self) -> Path:
        return self.dir / "status.json"

    @property
    def stop_path(self) -> Path:
        return self.dir / "STOP"

    def read_status(self) -> dict:
        try:
            return json.loads(self.status_path.read_text())
        except (OSError, json.JSONDecodeError):
            return {"session_id": self.session_id, "status": "unknown"}

    def write_status(self, **updates) -> dict:
        status = self.read_status()
        status.update(updates, session_id=self.session_id, updated_at=_now())
        tmp = self.status_path.with_suffix(".json.tmp")
        tmp.write_text(json.dumps(status))
        tmp.rename(self.status_path)
        return status

    def stop_requested(self) -> bool:
        return self.stop_path.exists()

    # ---- snapshots -------------------------------------------------------

    def snapshot_path(self, epoch: int) -> Path:
        return self.models_dir / f"epoch_{epoch:04d}.npz"

    @property
    def latest_path(self) -> Path:
        return self.models_dir / "latest.npz"

    def save_snapshot(self, epoch: int, params) -> None:
        from bioengine_tpu.runtime.convert import save_params_npz

        path = self.snapshot_path(epoch)
        save_params_npz(str(path), params)
        tmp = self.latest_path.with_suffix(".npz.tmp")
        shutil.copyfile(path, tmp)
        tmp.rename(self.latest_path)  # atomic: live inference never sees a partial file

    def snapshots(self) -> list[str]:
        return sorted(p.name for p in self.models_dir.glob("epoch_*.npz"))

    @property
    def train_state_path(self) -> Path:
        """Full TrainState (params + optimizer moments + step) so resume
        continues adamw where it left off instead of re-warming."""
        return self.models_dir / "train_state.msgpack"

    def save_train_state(self, state_bytes: bytes) -> None:
        tmp = self.train_state_path.with_suffix(".msgpack.tmp")
        tmp.write_bytes(state_bytes)
        tmp.rename(self.train_state_path)


class CellposeFinetune:
    def __init__(self, sessions_root: str = "~/.bioengine/cellpose-sessions"):
        self.sessions_root = Path(sessions_root).expanduser()
        self.sessions_root.mkdir(parents=True, exist_ok=True)
        self.sessions: dict[str, TrainingSession] = {}
        # serializes start/stop/restart/delete per session id — the busy
        # check can suspend (waiting out a task wind-down), so without
        # a lock two callers could both pass it and then both mutate.
        # value = [lock, refcount]; the entry is reclaimed when the last
        # holder/waiter leaves, so ids probed once don't accumulate
        self._locks: dict[str, list] = {}
        self._fwd_cache: dict[tuple, object] = {}  # features -> jitted forward
        self._recover_sessions()

    @contextlib.asynccontextmanager
    async def _lifecycle_lock(self, session_id: str):
        entry = self._locks.setdefault(session_id, [asyncio.Lock(), 0])
        entry[1] += 1
        try:
            async with entry[0]:
                yield
        finally:
            entry[1] -= 1
            if entry[1] == 0 and self._locks.get(session_id) is entry:
                del self._locks[session_id]

    def _recover_sessions(self) -> None:
        """Re-adopt session dirs from a previous replica life (the
        reference recovers sessions from disk the same way; training
        tasks do not survive, so running ones become 'interrupted')."""
        for d in self.sessions_root.iterdir():
            if d.name.startswith("."):
                # a '.{name}.deleting-*' dir is a failed start_training's
                # renamed-away tree whose threaded rmtree didn't finish
                # (crash/restart mid-delete) — sweep it, never adopt it.
                # Only OUR rename pattern: any other hidden directory
                # (.cache, .snapshots, ...) is not ours to delete.
                if ".deleting-" in d.name and d.is_dir():
                    shutil.rmtree(d, ignore_errors=True)
                continue
            if (d / "status.json").exists():
                try:
                    cfg = json.loads((d / "config.json").read_text())
                except (OSError, json.JSONDecodeError):
                    cfg = dict(DEFAULT_CONFIG)
                s = TrainingSession(self.sessions_root, d.name, cfg)
                if s.read_status().get("status") == "training":
                    s.write_status(
                        status="interrupted",
                        error="worker restarted during training",
                    )
                self.sessions[d.name] = s

    async def check_health(self):
        if not self.sessions_root.exists():
            raise RuntimeError("sessions root vanished")

    # ---- data handling ---------------------------------------------------

    @staticmethod
    def _prepare_images(images: list) -> np.ndarray:
        """-> (N, H, W, 2) float32, per-image 1-99 percentile normalized.
        Grayscale gets a zero second channel (cellpose channel
        convention: [cyto, nucleus])."""
        out = []
        for img in images:
            # always copy: normalization below is in-place and must not
            # write through to the caller's array
            a = np.array(img, np.float32, copy=True)
            if a.ndim == 2:
                a = np.stack([a, np.zeros_like(a)], axis=-1)
            elif a.ndim == 3 and a.shape[-1] == 1:
                a = np.concatenate([a, np.zeros_like(a)], axis=-1)
            elif a.ndim == 3 and a.shape[-1] > 2:
                a = a[..., :2]
            # per-channel percentiles — mixed-bit-depth channels (8-bit
            # cyto + 16-bit nucleus) must each land in [0, 1]
            for c in range(a.shape[-1]):
                lo, hi = np.percentile(a[..., c], [1, 99])
                a[..., c] = (a[..., c] - lo) / max(hi - lo, 1e-6)
            out.append(a)
        return np.stack(out)

    def _prepare_training_data(
        self, session: TrainingSession, images: list, labels: list
    ) -> None:
        """Normalize images, derive the backbone's targets from masks
        (flow fields for cellpose-family backbones, edt-prob +
        ray-distances for stardist), persist to the session's data dir
        (restart_training reuses them)."""
        x = self._prepare_images(images)
        masks = np.stack([np.asarray(m) for m in labels]).astype(np.int32)
        if masks.shape[:3] != x.shape[:3]:
            raise ValueError(
                f"images {x.shape[:3]} and labels {masks.shape[:3]} disagree"
            )
        if session.config.get("backbone") == "stardist":
            from bioengine_tpu.ops.stardist import masks_to_stardist

            cfg = session.config
            pairs = [
                masks_to_stardist(
                    m,
                    n_rays=int(cfg["n_rays"]),
                    max_dist=int(cfg["max_dist"]),
                )
                for m in masks
            ]
            targets = {
                "prob": np.stack([p for p, _ in pairs]),       # (N, H, W)
                "dist": np.stack([d for _, d in pairs]),       # (N, H, W, R)
            }
        else:
            from bioengine_tpu.ops.flows import masks_to_flows

            flows = np.stack([masks_to_flows(m) for m in masks])
            targets = {
                "flows": np.moveaxis(flows, 1, -1),            # (N, H, W, 2)
                "cellprob": (masks > 0).astype(np.float32),    # (N, H, W)
            }
        np.savez(session.data_dir / "train.npz", images=x, **targets)

    # ---- the train loop (runs in a thread) -------------------------------

    def _train_loop(self, session: TrainingSession, resume: bool) -> None:
        import jax
        import jax.numpy as jnp
        import optax

        from bioengine_tpu.models.cellpose import TrainState, make_train_step
        from bioengine_tpu.parallel.data_parallel import (
            jit_data_parallel_step, replicate, shard_batch,
        )
        from bioengine_tpu.parallel.mesh import make_mesh
        from bioengine_tpu.runtime.convert import load_params_npz

        cfg = session.config
        stardist = cfg.get("backbone") == "stardist"
        data = np.load(session.data_dir / "train.npz")
        images = data["images"]
        if stardist:
            t_a, t_b = data["prob"], data["dist"]          # (N,H,W), (N,H,W,R)
        else:
            t_a, t_b = data["flows"], data["cellprob"]     # (N,H,W,2), (N,H,W)
        n, H, W = images.shape[:3]
        model, divisor = build_model(cfg)
        # tile must divide through the encoder (pool stages / patch
        # grid) or the decoder output misaligns
        tile = min(cfg["tile"], H, W)
        if tile < divisor:
            raise ValueError(
                f"images ({H}x{W}) smaller than the model's minimum tile "
                f"{divisor} for this backbone config"
            )
        tile = (tile // divisor) * divisor

        # dp over every LEASED chip that divides the batch — the mesh
        # is built from the replica's lease, never jax.devices()[:dp]
        # (two replicas on one host would share the first chips while
        # the controller books them apart); no lease = stand-alone use
        lease = getattr(self, "bioengine_device_ids", None)
        if lease:
            from bioengine_tpu.runtime.engine import resolve_devices

            devices = resolve_devices(list(lease))
        else:
            devices = jax.local_devices()
        batch = cfg["batch_size"]
        dp = 1
        while dp * 2 <= len(devices) and batch % (dp * 2) == 0:
            dp *= 2
        mesh = make_mesh({"dp": dp}, devices[:dp])

        rng = np.random.default_rng(cfg["seed"])
        start_epoch = 0
        restored_state = None
        tx = optax.adamw(cfg["learning_rate"], weight_decay=cfg["weight_decay"])
        if resume and session.latest_path.exists():
            from flax import serialization

            params = load_params_npz(str(session.latest_path))
            start_epoch = len(session.snapshots())
            if session.train_state_path.exists():
                template = TrainState.create(model.apply, params, tx)
                restored_state = serialization.from_bytes(
                    template, session.train_state_path.read_bytes()
                )
        elif cfg.get("pretrained_path"):
            # fine-tune from converted foundation weights (the
            # reference's whole value proposition: start from cpsam,
            # ref main.py:2248) — validate the tree against the
            # architecture cheaply via eval_shape so a wrong checkpoint
            # fails loudly naming the mismatched leaves, not deep in jit
            params = load_params_npz(cfg["pretrained_path"])
            expect = jax.eval_shape(
                lambda: model.init(
                    jax.random.key(0),
                    jnp.zeros(
                        (1, tile, tile, _model_channels(cfg)), jnp.float32
                    ),
                )
            )["params"]
            _check_pretrained_tree(params, expect)
        else:
            params = model.init(
                jax.random.key(cfg["seed"]),
                jnp.zeros((1, tile, tile, _model_channels(cfg)), jnp.float32),
            )["params"]
        state = replicate(
            mesh,
            restored_state
            if restored_state is not None
            else TrainState.create(model.apply, params, tx),
        )
        if stardist:
            from bioengine_tpu.models.stardist import make_stardist_train_step

            step = jit_data_parallel_step(make_stardist_train_step(), mesh)
            R = t_b.shape[-1]
            # flips permute ray indices: rays live at angles 2*pi*r/R
            # with direction (sin, cos); x -> -x maps theta to pi-theta
            # (index R/2 - r), y -> -y maps theta to -theta (index -r)
            h_perm = (R // 2 - np.arange(R)) % R
            v_perm = (-np.arange(R)) % R
        else:
            step = jit_data_parallel_step(make_train_step(), mesh)

        def sample_batch():
            idx = rng.integers(0, n, size=batch)
            ys = rng.integers(0, H - tile + 1, size=batch)
            xs = rng.integers(0, W - tile + 1, size=batch)
            bi = np.empty((batch, tile, tile, 2), np.float32)
            ba = np.empty((batch, tile, tile, *t_a.shape[3:]), np.float32)
            bb = np.empty((batch, tile, tile, *t_b.shape[3:]), np.float32)
            for j, (i, y0, x0) in enumerate(zip(idx, ys, xs)):
                sl = np.s_[y0 : y0 + tile, x0 : x0 + tile]
                im, ta, tb = images[i][sl], t_a[i][sl], t_b[i][sl]
                if rng.random() < 0.5:  # horizontal flip
                    im, ta, tb = im[:, ::-1], ta[:, ::-1], tb[:, ::-1]
                    if stardist:
                        tb = tb[..., h_perm]       # dist rays remap
                    else:
                        ta = ta * np.array([1.0, -1.0], np.float32)  # x-flow
                if rng.random() < 0.5:  # vertical flip
                    im, ta, tb = im[::-1], ta[::-1], tb[::-1]
                    if stardist:
                        tb = tb[..., v_perm]
                    else:
                        ta = ta * np.array([-1.0, 1.0], np.float32)  # y-flow
                bi[j], ba[j], bb[j] = im, ta, tb
            return _to_model_channels(bi, cfg), ba, bb

        steps_per_epoch = max(1, n * max(H // tile, 1) * max(W // tile, 1) // batch)
        session.write_status(
            status="training",
            total_epochs=cfg["epochs"],
            current_epoch=start_epoch,
            steps_per_epoch=steps_per_epoch,
            mesh={"dp": dp},
        )
        losses = session.read_status().get("losses", [])
        for epoch in range(start_epoch, cfg["epochs"]):
            epoch_losses = []
            for _ in range(steps_per_epoch):
                if session.stop_requested():
                    session.write_status(status="stopped", current_epoch=epoch)
                    return
                bi, ba, bb = sample_batch()
                sharded = shard_batch(
                    mesh, (jnp.asarray(bi), jnp.asarray(ba), jnp.asarray(bb))
                )
                state, metrics = step(state, *sharded)
                epoch_losses.append(float(metrics["loss"]))
            mean_loss = float(np.mean(epoch_losses))
            losses.append(mean_loss)
            # per-epoch snapshot feeds live inference (ref main.py:1825-1835)
            session.save_snapshot(epoch, jax.device_get(state.params))
            from flax import serialization

            session.save_train_state(
                serialization.to_bytes(jax.device_get(state))
            )
            session.write_status(
                status="training",
                current_epoch=epoch + 1,
                losses=losses,
                last_loss=mean_loss,
            )
        session.write_status(status="completed", current_epoch=cfg["epochs"])

    async def _run_training(self, session: TrainingSession, resume: bool):
        try:
            await asyncio.to_thread(self._train_loop, session, resume)
        except Exception as e:
            session.write_status(status="failed", error=str(e))

    # ---- service API ------------------------------------------------------

    @schema_method
    async def get_default_config(self, context=None):
        """Training hyperparameters and their defaults."""
        return dict(DEFAULT_CONFIG)

    @schema_method
    async def start_training(
        self,
        train_images: list,
        train_labels: list,
        config: dict | None = None,
        session_id: str | None = None,
        context=None,
    ):
        """Start a fine-tuning session. ``train_images``: list of (H, W)
        or (H, W, C) arrays; ``train_labels``: instance-label masks of
        the same spatial shape. Returns the session id to poll with
        ``get_training_status``."""
        cfg = _merge_config(config)
        session_id = session_id or f"session-{uuid.uuid4().hex[:8]}"
        async with self._lifecycle_lock(session_id):
            existing = self.sessions.get(session_id)
            if existing is not None and await self._busy(existing):
                raise RuntimeError(f"session '{session_id}' already training")
            # a reused id is a fresh run: stale snapshots/data would poison
            # restart_training's epoch counting and live inference
            old_dir = self.sessions_root / session_id
            if old_dir.exists():
                await asyncio.to_thread(shutil.rmtree, old_dir)
            session = TrainingSession(self.sessions_root, session_id, cfg)
            # claim the id with ``preparing`` set before releasing the
            # lock — other mutators fail fast instead of queueing for
            # the whole (potentially long) data-prep below
            session.preparing = True
            self.sessions[session_id] = session
        try:
            (session.dir / "config.json").write_text(json.dumps(cfg))
            session.write_status(
                status="initializing", started_at=_now(), losses=[],
                n_images=len(train_images),
            )
            await asyncio.to_thread(
                self._prepare_training_data,
                session, train_images, train_labels,
            )
            # spawn before clearing ``preparing`` so there is no instant
            # where the session is neither preparing nor tracked by a task
            session.task = asyncio.create_task(
                self._run_training(session, False)
            )
        except BaseException:
            self.sessions.pop(session_id, None)
            # don't leave a half-initialized dir for _recover_sessions
            # to re-adopt as a ghost session after a restart. Rename
            # synchronously (atomic, cheap) so a concurrent retry of the
            # same id never races the delete of a live path, then delete
            # the renamed tree in a thread so a large half-written data
            # dir can't stall the event loop
            doomed = session.dir.with_name(
                f".{session.dir.name}.deleting-{uuid.uuid4().hex[:8]}"
            )
            try:
                session.dir.rename(doomed)
            except OSError:
                doomed = None
            if doomed is not None:
                await asyncio.to_thread(
                    shutil.rmtree, doomed, ignore_errors=True
                )
            raise
        finally:
            session.preparing = False
        return {"session_id": session_id, "status": "started"}

    @schema_method
    async def stop_training(self, session_id: str, context=None):
        """Request a graceful stop (checked per batch, like the
        reference's stop-file, ref main.py:1278-1360)."""
        async with self._lifecycle_lock(session_id):
            session = self._get_session(session_id)
            session.stop_path.touch()
            if session.task:
                await asyncio.wait([session.task], timeout=30)
            return session.read_status()

    @schema_method
    async def restart_training(self, session_id: str, context=None):
        """Resume a stopped/interrupted/failed session from its latest
        snapshot (ref main.py:4117)."""
        async with self._lifecycle_lock(session_id):
            session = self._get_session(session_id)
            if await self._busy(session):
                raise RuntimeError(f"session '{session_id}' is still running")
            if not (session.data_dir / "train.npz").exists():
                raise RuntimeError(
                    f"session '{session_id}' has no persisted training data"
                )
            session.stop_path.unlink(missing_ok=True)
            session.write_status(status="initializing", error=None)
            session.task = asyncio.create_task(
                self._run_training(session, True)
            )
        return {"session_id": session_id, "status": "restarted"}

    @schema_method
    async def get_training_status(self, session_id: str, context=None):
        """The session's status.json: state, epoch progress, losses."""
        return self._get_session(session_id).read_status()

    @schema_method
    async def list_sessions(self, context=None):
        """All sessions with their current status and snapshot count."""
        return [
            {
                **s.read_status(),
                "snapshots": len(s.snapshots()),
            }
            for s in self.sessions.values()
        ]

    async def _busy(self, session) -> bool:
        """True if the session must not be mutated right now.

        status.json is written from inside the train thread, so a
        terminal status can land a beat before the asyncio task itself
        completes — callers that gate on "not training" wait out that
        wind-down here instead of rejecting a session the status file
        already reports finished. Callers must hold the session's
        lifecycle lock: this method can suspend, and the lock is what
        keeps a concurrent mutator from acting in that window.

        A task-less, non-preparing session (re-adopted after an app
        restart, including one that crashed mid-initialization) has
        nothing running in this process and is never busy."""
        if session.preparing:
            return True
        if session.task is None or session.task.done():
            return False
        if session.read_status().get("status") not in _TERMINAL_STATES:
            return True
        try:
            await asyncio.wait_for(asyncio.shield(session.task), timeout=30)
        except asyncio.TimeoutError:
            return True
        return False

    @schema_method
    async def delete_session(self, session_id: str, context=None):
        """Remove a session directory (must not be training)."""
        async with self._lifecycle_lock(session_id):
            session = self._get_session(session_id)
            if await self._busy(session):
                raise RuntimeError(f"stop session '{session_id}' first")
            # deregister first so infer/export on this id fail fast
            # instead of racing the threaded rmtree below
            self.sessions.pop(session_id, None)
            await asyncio.to_thread(
                shutil.rmtree, session.dir, ignore_errors=True
            )
        return {"deleted": session_id}

    @schema_method
    async def infer(
        self,
        session_id: str,
        images: list,
        cellprob_threshold: float = 0.0,
        min_size: int = 15,
        context=None,
    ):
        """Segment images with the session's latest snapshot — live
        inference against a training run works because snapshots are
        written atomically per epoch."""
        session = self._get_session(session_id)
        if not session.latest_path.exists():
            raise RuntimeError(
                f"session '{session_id}' has no snapshot yet"
            )
        try:
            masks = await asyncio.to_thread(
                self._infer, session, images, cellprob_threshold, min_size
            )
        except FileNotFoundError as exc:
            # an in-flight call can race delete_session's threaded rmtree
            # after the id is deregistered — surface a clean error
            raise RuntimeError(f"session '{session_id}' was deleted") from exc
        return {
            "masks": masks,
            "n_cells": [int(m.max()) for m in masks],
            "snapshot": session.snapshots()[-1] if session.snapshots() else None,
        }

    def _load_snapshot(self, session):
        from bioengine_tpu.runtime.convert import load_params_npz

        return load_params_npz(str(session.latest_path))

    def _predict_raw(self, session, x: np.ndarray, params=None) -> np.ndarray:
        """(N, H, W, 2) prepared batch -> raw network output:
        (N, H, W, 3) (dy, dx, cellprob logits) for cellpose-family
        backbones, (N, H, W, 1 + n_rays) (prob logit, ray distances)
        for stardist. ``params`` preloaded via ``_load_snapshot`` keeps
        multi-pass callers (infer_3d's three orientations) on ONE
        snapshot even while training is writing new ones; None loads
        the latest."""
        import jax

        from bioengine_tpu.runtime.buckets import bucket_shape, crop_to, pad_to

        cfg = session.config
        model, divisor = build_model(cfg)
        # one jitted forward per architecture: params are an argument, so
        # per-epoch snapshots and repeated infer calls reuse the compiled
        # program instead of retracing a fresh lambda every request
        arch_key = (
            cfg.get("backbone", "unet"),
            tuple(cfg["features"]),
            cfg.get("patch_size"), cfg.get("dim"),
            cfg.get("depth"), cfg.get("num_heads"),
            cfg.get("n_rays"),
            # cpsam-only knobs change the architecture too — without
            # them two cpsam sessions differing only in e.g.
            # window_size would share one compiled model
            *(
                tuple(cfg[k]) if isinstance(cfg.get(k), (list, tuple))
                else cfg.get(k)
                for k in _CPSAM_KEYS
            ),
        )
        if arch_key not in self._fwd_cache:
            # compiled-forward memo: bounded by distinct architecture
            # tuples, and evicting on session delete would retrigger an
            # XLA compile for siblings sharing the arch
            # bioengine: ignore[BE-LIFE-401]
            self._fwd_cache[arch_key] = jax.jit(
                lambda p, a, m=model: m.apply({"params": p}, a)
            )
        fwd = self._fwd_cache[arch_key]
        if params is None:
            params = self._load_snapshot(session)
        x = _to_model_channels(x, cfg)
        H, W = x.shape[1:3]
        bh, bw = bucket_shape((H, W), divisor=divisor)
        pred = np.asarray(fwd(params, pad_to(x, (bh, bw))))
        return crop_to(pred, (H, W))

    def _infer(self, session, images, cellprob_threshold, min_size):
        pred = self._predict_raw(session, self._prepare_images(images))
        if session.config.get("backbone") == "stardist":
            from bioengine_tpu.ops.stardist import (
                predictions_to_masks_stardist,
            )

            # the caller-facing threshold is a LOGIT for both families
            # (0.0 = probability 0.5); stardist's NMS takes probability
            prob_threshold = float(1.0 / (1.0 + np.exp(-cellprob_threshold)))
            return [
                predictions_to_masks_stardist(
                    p, prob_threshold=prob_threshold, min_size=min_size
                )
                for p in pred
            ]
        from bioengine_tpu.ops.flows import predictions_to_masks

        return [
            predictions_to_masks(
                p, cellprob_threshold=cellprob_threshold, min_size=min_size
            )
            for p in pred
        ]

    @schema_method
    async def infer_3d(
        self,
        session_id: str,
        volumes: list,
        cellprob_threshold: float = 0.0,
        min_size: int = 15,
        anisotropy: float = 1.0,
        context=None,
    ):
        """Segment (D, H, W) grayscale volumes with the session's 2D
        model via the cellpose ``do_3D`` recipe: the network runs over
        yx, zx, and zy slice orientations, shared flow components are
        averaged into one (dz, dy, dx) field, and voxels are followed
        to 3D sinks (ops/flows.py). ``anisotropy`` = z-spacing /
        xy-spacing: the stack is resampled along z by this factor first
        so cells appear isotropic to the 2D network, and the masks are
        resampled back. The reference delegates all of this to the
        upstream cellpose library; here it is first-class and the flow
        following runs jitted on TPU."""
        session = self._get_session(session_id)
        if session.config.get("backbone") == "stardist":
            raise RuntimeError(
                "infer_3d needs flow-field outputs (the cellpose do_3D "
                "recipe); the stardist backbone predicts 2D polygons — "
                "use infer per z-slice instead"
            )
        if not session.latest_path.exists():
            raise RuntimeError(f"session '{session_id}' has no snapshot yet")
        if anisotropy <= 0:
            raise ValueError(f"anisotropy must be positive, got {anisotropy}")
        try:
            masks = await asyncio.to_thread(
                self._infer_3d, session, volumes, cellprob_threshold,
                min_size, anisotropy,
            )
        except FileNotFoundError as exc:
            # same delete_session race as ``infer``
            raise RuntimeError(f"session '{session_id}' was deleted") from exc
        return {
            "masks": masks,
            "n_cells": [int(m.max()) for m in masks],
            "snapshot": session.snapshots()[-1] if session.snapshots() else None,
        }

    def _infer_3d(
        self, session, volumes, cellprob_threshold, min_size, anisotropy=1.0
    ):
        from scipy import ndimage as ndi

        from bioengine_tpu.ops.flows import (
            FLOW_SCALE,
            aggregate_orthogonal_flows,
            filter_and_relabel,
            masks_from_flows,
        )

        # one snapshot for the whole request: the three orientation
        # passes must not mix weights when training is concurrently
        # writing new epochs
        params = self._load_snapshot(session)
        out = []
        for vol in volumes:
            v = np.array(vol, np.float32, copy=True)
            if v.ndim != 3:
                raise ValueError(
                    f"infer_3d expects (D, H, W) grayscale volumes, "
                    f"got shape {v.shape}"
                )
            orig_depth = v.shape[0]
            if anisotropy != 1.0:
                # make voxels isotropic for the 2D net's zx/zy passes;
                # the explicit factor guarantees >= 1 output plane for
                # tiny anisotropy values
                new_depth = max(1, int(round(orig_depth * anisotropy)))
                v = ndi.zoom(v, (new_depth / orig_depth, 1.0, 1.0), order=1)
            # actual resampling ratio (rounding can make it differ from
            # the requested anisotropy, including a no-op) — min_size
            # scales by this, not by the raw parameter
            depth_ratio = v.shape[0] / orig_depth
            # normalize the whole volume once — per-slice percentile
            # normalization would flicker along the slicing axis
            lo, hi = np.percentile(v, [1, 99])
            v = (v - lo) / max(hi - lo, 1e-6)
            preds = []
            for axes in ((0, 1, 2), (1, 0, 2), (2, 0, 1)):  # yx, zx, zy
                slices = np.ascontiguousarray(np.transpose(v, axes))
                x = np.stack([slices, np.zeros_like(slices)], axis=-1)
                preds.append(self._predict_raw(session, x, params=params))
            flow, cellprob = aggregate_orthogonal_flows(*preds)
            # min_size is a caller-resolution voxel count: at the
            # z-resampled resolution it scales by the actual depth
            # ratio, and the authoritative filter runs after resampling
            # back
            masks = masks_from_flows(
                flow / FLOW_SCALE,
                cellprob,
                cellprob_threshold=cellprob_threshold,
                min_size=max(1, int(round(min_size * depth_ratio))),
            )
            if masks.shape[0] != orig_depth:
                # nearest-neighbour back to the caller's z sampling —
                # labels must not be interpolated
                masks = ndi.zoom(
                    masks, (orig_depth / masks.shape[0], 1.0, 1.0), order=0
                )
                masks = masks[:orig_depth]
                if masks.shape[0] < orig_depth:
                    masks = np.pad(
                        masks,
                        ((0, orig_depth - masks.shape[0]), (0, 0), (0, 0)),
                        mode="edge",
                    )
                # resampling can erase whole instances: re-filter and
                # re-label at the caller's resolution so n_cells ==
                # masks.max() stays truthful
                masks = filter_and_relabel(masks, min_size)
            out.append(masks)
        return out

    @schema_method
    async def export_model(
        self,
        session_id: str,
        model_name: str | None = None,
        context=None,
    ):
        """Package the session's latest snapshot as a model-runner-ready
        ``jax_params`` model directory (rdf.yaml + weights.npz + test
        tensors) — the TPU analog of the reference's BioImage Model Zoo
        export (ref main.py:4413+, model_template.py:18)."""
        session = self._get_session(session_id)
        if not session.latest_path.exists():
            raise RuntimeError(f"session '{session_id}' has no snapshot")
        cfg = session.config
        stardist = cfg.get("backbone") == "stardist"
        family = "stardist" if stardist else "cellpose"
        name = model_name or f"{family}-{session_id}"
        export_dir = self.sessions_root / "exports" / name
        export_dir.mkdir(parents=True, exist_ok=True)
        await asyncio.to_thread(
            shutil.copyfile, session.latest_path, export_dir / "weights.npz"
        )
        rdf = {
            "type": "model",
            "name": name,
            "description": (
                f"StarDist star-convex polygon model (prob + "
                f"{cfg.get('n_rays')} ray distances) fine-tuned in "
                f"BioEngine-TPU session {session_id}"
                if stardist
                else f"Cellpose flow-field model fine-tuned in "
                f"BioEngine-TPU session {session_id}"
            ),
            "tags": [family, "segmentation", "fine-tuned"],
            "inputs": [{"name": "input0", "axes": "byxc"}],
            "outputs": [{"name": "output0", "axes": "byxc"}],
            "weights": {
                "jax_params": {
                    "source": "weights.npz",
                    "architecture": _arch_entry(cfg),
                }
            },
            "training": {
                "session_id": session_id,
                "config": cfg,
                "final_loss": session.read_status().get("last_loss"),
            },
        }
        (export_dir / "rdf.yaml").write_text(yaml.safe_dump(rdf))
        return {
            "model_path": str(export_dir),
            "name": name,
            "weights_format": "jax_params",
        }

    def _get_session(self, session_id: str) -> TrainingSession:
        if session_id not in self.sessions:
            raise KeyError(
                f"unknown session '{session_id}' "
                f"(have: {sorted(self.sessions)})"
            )
        return self.sessions[session_id]
