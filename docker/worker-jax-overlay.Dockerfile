# Lightweight overlay over a published BioEngine-TPU worker image:
# swaps only the jax/libtpu pin without rebuilding system packages,
# Python, the native store, or the rest of the dependency tree — the
# analog of the reference's Ray-overlay image
# (ref docker/worker-ray-overlay.Dockerfile: same motivation, a
# version-locked runtime dependency that must match the environment it
# connects to; here it is the jax/libtpu pair that must match the TPU
# VM's driver generation instead of a Ray cluster's version).
#
# Build:
#   docker build \
#       --build-arg BIOENGINE_IMAGE=ghcr.io/OWNER/bioengine-tpu-worker:latest \
#       --build-arg JAX_VERSION=0.9.0 \
#       -f docker/worker-jax-overlay.Dockerfile \
#       -t bioengine-tpu-worker:jax0.9.0 .
#
# BIOENGINE_IMAGE: the published image used as the base.
# JAX_VERSION:     the exact jax release to swap in; libtpu resolves to
#   the matching build from the jax releases index.

ARG BIOENGINE_IMAGE=ghcr.io/aicell-lab/bioengine-tpu-worker:latest
FROM ${BIOENGINE_IMAGE}

ARG JAX_VERSION=0.9.0
RUN pip install --no-cache-dir "jax[tpu]==${JAX_VERSION}" \
    -f https://storage.googleapis.com/jax-releases/libtpu_releases.html

ENV BIOENGINE_JAX_VERSION=${JAX_VERSION}
