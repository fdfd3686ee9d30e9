# gordo-tpu image — the single image every pod in the generated workflow
# runs (template `{{ image }}`): TPU builder workers, model servers, clients,
# and the workflow generator itself.
#
# TPU-native counterpart of the reference's gordo-base image
# (/root/reference/Dockerfile:1-90): instead of TensorFlow wheels it installs
# jax[tpu] (libtpu via Google's release index), and the entrypoints are the
# gordo-tpu CLI. Runs unchanged on CPU hosts (JAX_PLATFORMS=cpu) for tests
# and the workflow-generator step.

ARG PYTHON_VERSION=3.12

FROM python:${PYTHON_VERSION}-slim AS builder
COPY . /code
WORKDIR /code
RUN pip install --no-cache-dir build \
    && python -m build --sdist --outdir /dist \
    && mv /dist/$(ls /dist | head -1) /dist/gordo-tpu-packed.tar.gz

FROM python:${PYTHON_VERSION}-slim

RUN groupadd -g 999 gordo && useradd -r -m -u 999 -g gordo gordo

# jax first: the biggest layer, cached independently of framework changes.
# The tpu extra pulls libtpu from Google's release index; on non-TPU hosts
# jax falls back to CPU at runtime.
ARG JAX_VERSION=
RUN pip install --no-cache-dir \
    "jax[tpu]${JAX_VERSION:+==${JAX_VERSION}}" \
    -f https://storage.googleapis.com/jax-releases/libtpu_releases.html

# kubectl + argo: used by the workflow's cleanup/throttle script steps and
# the deploy gate (scripts/run_workflow_and_argo.sh)
RUN apt-get update && apt-get install -y --no-install-recommends curl \
    && rm -rf /var/lib/apt/lists/*
ARG KUBECTL_VERSION=v1.30.3
ARG ARGO_VERSION=v3.5.8
RUN curl -sSL -o /usr/local/bin/kubectl \
      "https://dl.k8s.io/release/${KUBECTL_VERSION}/bin/linux/amd64/kubectl" \
    && chmod +x /usr/local/bin/kubectl \
    && curl -sSL -o /tmp/argo.gz \
      "https://github.com/argoproj/argo-workflows/releases/download/${ARGO_VERSION}/argo-linux-amd64.gz" \
    && gzip -d < /tmp/argo.gz > /usr/local/bin/argo \
    && chmod +x /usr/local/bin/argo && rm /tmp/argo.gz

COPY --from=builder /dist/gordo-tpu-packed.tar.gz /tmp/
RUN pip install --no-cache-dir /tmp/gordo-tpu-packed.tar.gz \
    && rm /tmp/gordo-tpu-packed.tar.gz

# pod entrypoints: `build` waits for the shared model volume then trains
COPY build.sh /usr/local/bin/build
COPY scripts/run_workflow_and_argo.sh /usr/local/bin/run_workflow_and_argo.sh
RUN chmod +x /usr/local/bin/build /usr/local/bin/run_workflow_and_argo.sh

USER gordo
WORKDIR /home/gordo
# the persistent XLA compile cache is placed from outside: an installed
# package has no writable checkout to default into (util/xla_cache.py).
# Mount a volume here to keep compiles across pod restarts.
ENV JAX_COMPILATION_CACHE_DIR=/home/gordo/.cache/jax
CMD ["gordo-tpu", "--help"]
