from bioengine_tpu.runtime.buckets import (
    bucket_shape,
    crop_to,
    fill_bucketed,
    pad_to,
)
from bioengine_tpu.runtime.engine import EngineConfig, InferenceEngine
from bioengine_tpu.runtime.pipeline import (
    DispatchExecutor,
    PipelineStats,
    StagingPool,
    TileStream,
)
from bioengine_tpu.runtime.program_cache import (
    CompiledProgramCache,
    default_program_cache,
)
from bioengine_tpu.runtime.weight_stream import (
    StreamedWeightLoader,
    load_manifest,
    skeleton_from_manifest,
    write_manifest,
)

__all__ = [
    "bucket_shape",
    "fill_bucketed",
    "pad_to",
    "crop_to",
    "EngineConfig",
    "InferenceEngine",
    "DispatchExecutor",
    "PipelineStats",
    "StagingPool",
    "TileStream",
    "CompiledProgramCache",
    "default_program_cache",
    "StreamedWeightLoader",
    "load_manifest",
    "skeleton_from_manifest",
    "write_manifest",
]
