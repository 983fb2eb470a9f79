# Container image for the coordinator and worker hosts.
# The reference left containerization as future scope
# (implementation.md:85-103); this image runs either role:
#   docker run ... dlt-coordinator --metrics-port 9100
#   docker run ... dlt-host --host <coordinator> --port 65432
# On TPU VMs, base on a TPU-enabled JAX image instead and the same
# entry points apply (jax[tpu] resolves the libtpu runtime).  There, set
# JAX_COMPILATION_CACHE_DIR to a directory on a volume that outlives the
# container: it is the one way to place the XLA compilation cache (unset,
# a TPU run caches inside the installed tree, which dies with the
# container), and a restarted server then skips its first compiles.
FROM python:3.12-slim

# g++ enables the native IO tier (distributed_llms_tpu/native); the package
# falls back to pure-Python IO without it, so this is an optimization.
RUN apt-get update && apt-get install -y --no-install-recommends g++ \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY pyproject.toml README.md ./
COPY distributed_llms_tpu ./distributed_llms_tpu
RUN pip install --no-cache-dir .[hf]

# control plane / Prometheus exposition
EXPOSE 65432 9100

ENTRYPOINT ["dlt-coordinator"]
CMD ["--serve", "--metrics-port", "9100"]
