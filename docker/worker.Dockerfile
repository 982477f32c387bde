# BioEngine-TPU worker image — the TPU answer to the reference's
# docker/worker.Dockerfile (CUDA via torch inside Ray runtime envs).
# Runs on Cloud TPU VMs / GKE TPU node pools: jax[tpu] talks to the
# chips through libtpu + /dev/accel*, so the image needs no CUDA stack.
#
#   docker build -f docker/worker.Dockerfile -t bioengine-tpu-worker .
#
# On a TPU VM run with device + shm access:
#   docker run --privileged --network host \
#     -v $HOME/.bioengine:/home/.bioengine bioengine-tpu-worker \
#     python -m bioengine_tpu.worker --mode single-machine

FROM python:3.11-slim

ENV PYTHONUNBUFFERED=1 \
    PYTHONDONTWRITEBYTECODE=1 \
    PIP_NO_CACHE_DIR=1

# build-essential: the native shared-memory object store
# (native/object_store.cpp) compiles in-image so first use never needs
# a toolchain at runtime. curl: compose healthchecks.
RUN apt-get update && apt-get install -y --no-install-recommends \
    build-essential \
    curl \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app

# Dependency layer first — package source changes don't invalidate it.
COPY docker/requirements-worker.txt /app/
RUN pip install -U pip && pip install -r requirements-worker.txt

COPY bioengine_tpu/ /app/bioengine_tpu/
COPY native/ /app/native/
COPY apps/ /app/apps/
COPY pyproject.toml README.md /app/

RUN pip install --no-deps .

# Pre-build the native object store so replicas never race the first
# `make` at runtime.
RUN make -C /app/native

# ---------------------------------------------------------------------------
# jax + libtpu last, controlled by JAX_VERSION: bumping the jax/libtpu
# pair (they must match) rebuilds only this layer, mirroring the
# reference's Ray-last layering trick (ref docker/worker.Dockerfile).
#
#   docker build --build-arg JAX_VERSION=0.9.0 \
#     -f docker/worker.Dockerfile -t bioengine-tpu-worker:dev .
# ---------------------------------------------------------------------------
ARG JAX_VERSION=0.9.0
RUN pip install "jax[tpu]==${JAX_VERSION}" \
    -f https://storage.googleapis.com/jax-releases/libtpu_releases.html

ENV BIOENGINE_JAX_VERSION=${JAX_VERSION}

CMD ["/bin/bash"]
