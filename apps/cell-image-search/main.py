"""Cell Morphology Search Engine — TPU-native.

API parity with the reference's CellImageSearch deployment
(ref apps/cell-image-search/main.py:1051-1522): ping, get_index_stats,
list_datasets / add_dataset / remove_dataset, start_ingestion /
get_ingestion_status / stop_ingestion / get_active_sessions, search,
get_umap_preview (projection), project_query_onto_umap.

TPU redesign (SURVEY.md §2.2): the embedder is the framework's
dp-sharded jitted Flax ViT (embedder.py), similarity search runs on
the MXU for flat indexes and over IVF/PQ lists otherwise (index.py),
ingestion streams from the egress-free datasets plane instead of S3
(ingestion.py).
"""

from __future__ import annotations

import asyncio
import time
from pathlib import Path
from typing import Any, Optional

import numpy as np

from bioengine_tpu.rpc import schema_method


class CellImageSearch:
    def __init__(
        self,
        workspace_dir: str = "~/.bioengine/cell-image-search",
        weights_path: Optional[str] = None,
        batch_bucket: int = 64,
        crop_size: int = 224,
        n_crops_per_image: int = 50,
    ):
        from embedder import ViTEmbedder

        self.workspace_dir = Path(workspace_dir).expanduser()
        self.workspace_dir.mkdir(parents=True, exist_ok=True)
        self.embedder = ViTEmbedder(
            weights_path=weights_path, batch_bucket=batch_bucket
        )
        self.crop_size = crop_size
        self.n_crops_per_image = n_crops_per_image
        self.started_at = time.time()
        self._index = None
        self._metadata = None
        self._index_info: dict = {}
        self._sessions: dict[str, asyncio.Task] = {}
        self._index_lock = asyncio.Lock()

    # ---- lifecycle hooks --------------------------------------------------

    async def async_init(self):
        # the replica lifecycle injects the chip lease before async_init
        self.embedder.device_ids = getattr(self, "bioengine_device_ids", None)
        await self._try_load_index()

    async def test_deployment(self):
        """Embed one synthetic image and round-trip the pipeline."""
        from ingestion import make_synthetic_images

        _, img = next(iter(make_synthetic_images(n_images=1, size=256)))
        emb = await asyncio.to_thread(self.embedder.embed_single, img)
        assert emb.shape == (self.embedder.EMBED_DIM,), emb.shape
        norm = float(np.linalg.norm(emb))
        assert abs(norm - 1.0) < 1e-3, f"embedding not unit-norm: {norm}"

    async def check_health(self):
        if not self.embedder.loaded:
            raise RuntimeError("embedder not loaded")

    async def _try_load_index(self) -> bool:
        from index import load_index

        try:
            index, df, info = await asyncio.to_thread(
                load_index, self.workspace_dir
            )
        except FileNotFoundError:
            return False
        self._index, self._metadata, self._index_info = index, df, info
        return True

    # ---- status -----------------------------------------------------------

    @schema_method
    async def ping(self, context=None):
        """Liveness + device/backend summary."""
        import jax

        return {
            "status": "ok",
            "uptime_seconds": time.time() - self.started_at,
            "backend": jax.default_backend(),
            "n_devices": jax.local_device_count(),
            "embedder_loaded": self.embedder.loaded,
            "pretrained": self.embedder.pretrained,
            "index_loaded": self._index is not None,
        }

    @schema_method
    async def get_index_stats(self, context=None):
        """Index size/type/build stats, or {loaded: False}."""
        if self._index is None and not await self._try_load_index():
            return {"loaded": False, "n_cells": 0}
        return {
            "loaded": True,
            "n_cells": self._index.ntotal,
            "index_type": self._index.kind,
            **self._index_info,
        }

    # ---- dataset registry --------------------------------------------------

    @schema_method
    async def list_datasets(self, context=None):
        """Registered ingestion sources + datasets-plane datasets."""
        from ingestion import load_registry

        registered = load_registry(self.workspace_dir)
        remote = []
        client = getattr(self, "bioengine_datasets", None)
        if client is not None and client.available:
            try:
                remote = await client.list_datasets()
            except Exception:
                remote = []
        return {"registered": registered, "data_server": remote}

    @schema_method
    async def add_dataset(
        self,
        name: str,
        source: str = "synthetic",
        path: Optional[str] = None,
        n_images: int = 8,
        image_size: int = 896,
        context=None,
    ):
        """Register an ingestion source. source: 'synthetic' (demo
        generator), 'local' (directory on the worker), or 'datasets'
        (a dataset served by the framework's data server)."""
        from ingestion import upsert_registry

        if source not in ("synthetic", "local", "datasets"):
            raise ValueError(f"unknown source '{source}'")
        if source == "local" and not path:
            raise ValueError("source 'local' requires path")
        entry = {
            "name": name,
            "source": source,
            "path": path,
            "n_images": n_images,
            "image_size": image_size,
            "added_at": time.time(),
        }
        upsert_registry(self.workspace_dir, entry)
        return {"added": True, "dataset": entry}

    @schema_method
    async def remove_dataset(self, name: str, context=None):
        """Drop a dataset from the registry."""
        from ingestion import load_registry, save_registry

        registry = load_registry(self.workspace_dir)
        kept = [r for r in registry if r.get("name") != name]
        save_registry(self.workspace_dir, kept)
        return {"removed": len(kept) < len(registry)}

    # ---- ingestion ---------------------------------------------------------

    @schema_method
    async def start_ingestion(
        self,
        dataset_name: str,
        session_id: Optional[str] = None,
        n_crops_per_image: Optional[int] = None,
        context=None,
    ):
        """Launch background ingestion of a registered dataset; returns
        the session id to poll with get_ingestion_status."""
        from ingestion import (
            load_registry,
            run_ingestion,
            session_dir,
            write_status,
            IngestionStatus,
        )

        entry = next(
            (
                r
                for r in load_registry(self.workspace_dir)
                if r.get("name") == dataset_name
            ),
            None,
        )
        if entry is None:
            raise ValueError(
                f"dataset '{dataset_name}' not registered — add_dataset first"
            )
        session_id = session_id or f"ingest-{int(time.time())}"
        live = self._sessions.get(session_id)
        if live is not None and not live.done():
            raise RuntimeError(f"session '{session_id}' already running")
        # prune finished task handles so the registry tracks only live
        # runs — session history lives on disk (status.json), not here
        for sid in [s for s, t in self._sessions.items() if t.done()]:
            self._sessions.pop(sid, None)
        # fresh session dir per run
        sdir = session_dir(self.workspace_dir, session_id)
        if sdir.exists():
            import os
            import shutil

            # rename synchronously so a concurrent start for the same
            # session_id can't pass the liveness guard mid-delete and
            # race on the session dir; delete the renamed tree off-loop
            doomed = sdir.with_name(f".{sdir.name}.deleting-{os.getpid()}")
            sdir.rename(doomed)
            await asyncio.to_thread(shutil.rmtree, doomed)
        write_status(
            self.workspace_dir, session_id,
            IngestionStatus.WAITING, "Queued",
            dataset_name=dataset_name,
        )
        dataset = dict(entry)
        if dataset["source"] == "datasets":
            dataset["client"] = getattr(self, "bioengine_datasets", None)

        async def _run():
            from ingestion import IngestionStatus, write_status

            try:
                async with self._index_lock:
                    await run_ingestion(
                        workspace_dir=self.workspace_dir,
                        session_id=session_id,
                        dataset=dataset,
                        embedder=self.embedder,
                        crop_size=self.crop_size,
                        n_crops_per_image=(
                            n_crops_per_image or self.n_crops_per_image
                        ),
                        batch_bucket=self.embedder.batch_bucket,
                    )
                    await self._try_load_index()
            except Exception as e:
                write_status(
                    self.workspace_dir, session_id,
                    IngestionStatus.FAILED, f"Error: {e}",
                )

        self._sessions[session_id] = asyncio.create_task(_run())
        return {"session_id": session_id, "status": "started"}

    @schema_method
    async def get_ingestion_status(self, session_id: str, context=None):
        """Poll a session's status.json."""
        from ingestion import read_status

        return read_status(self.workspace_dir, session_id)

    @schema_method
    async def stop_ingestion(self, session_id: str, context=None):
        """Request a running session to stop (between batches)."""
        from ingestion import request_stop

        request_stop(self.workspace_dir, session_id)
        return {"session_id": session_id, "stop_requested": True}

    @schema_method
    async def get_active_sessions(self, context=None):
        """All known sessions with their latest status."""
        from ingestion import read_status, session_dir

        root = session_dir(self.workspace_dir, "x").parent
        sessions = {}
        if root.exists():
            for d in sorted(root.iterdir()):
                # skip '.{name}.deleting-*' rename-away trees (crashed
                # mid-delete) and other hidden dirs — not sessions
                if d.is_dir() and not d.name.startswith("."):
                    sessions[d.name] = read_status(
                        self.workspace_dir, d.name
                    )
        return sessions

    # ---- search ------------------------------------------------------------

    @schema_method
    async def search(
        self,
        image: Any = None,
        image_bytes: Optional[bytes] = None,
        top_k: int = 20,
        context=None,
    ):
        """Find morphologically similar cells. ``image`` is any
        microscopy array (1-5 channels); ``image_bytes`` a PNG/JPEG/
        TIFF. Returns ranked matches with metadata + the query's 2-D
        map position."""
        from index import project_query, search_index
        from normalizer import decode_image_bytes

        if self._index is None and not await self._try_load_index():
            raise RuntimeError("no index built yet — run ingestion first")
        if image is None and image_bytes is None:
            raise ValueError("provide image or image_bytes")
        if image is None:
            image = decode_image_bytes(image_bytes)
        t0 = time.time()
        query = await asyncio.to_thread(
            self.embedder.embed_single, np.asarray(image)
        )
        t_embed = time.time() - t0
        t0 = time.time()
        results = await asyncio.to_thread(
            search_index, self._index, self._metadata, query, top_k
        )
        t_search = time.time() - t0
        return {
            "results": results,
            "n_results": len(results),
            "embed_ms": round(t_embed * 1000, 2),
            "search_ms": round(t_search * 1000, 2),
            "query_projection": project_query(self.workspace_dir, query),
        }

    # ---- projection (UMAP-analog) -----------------------------------------

    @schema_method
    async def get_umap_preview(
        self,
        n_samples: int = 10_000,
        force_recompute: bool = False,
        context=None,
    ):
        """2-D projection of an index sample for the dashboard scatter
        (PCA projector, cached with components so queries map into the
        same space)."""
        from index import compute_projection

        return await asyncio.to_thread(
            compute_projection,
            self.workspace_dir,
            n_samples,
            42,
            force_recompute,
        )

    @schema_method
    async def project_query_onto_umap(
        self, image: Any, context=None
    ):
        """Embed an image and return its position on the cached 2-D map."""
        from index import project_query

        query = await asyncio.to_thread(
            self.embedder.embed_single, np.asarray(image)
        )
        pos = project_query(self.workspace_dir, query)
        if pos is None:
            raise RuntimeError(
                "no projection cache — call get_umap_preview first"
            )
        return pos
