# Container image for the standalone scheduler process — the run-surface
# analog of the reference's Dockerfile (/root/reference/Dockerfile:1-20,
# which containerizes the Go simulator next to etcd).  Here there is no
# etcd sidecar: L0 durability is the in-process WAL store, mounted as a
# volume (docker-compose.yml).
#
# The image runs the scalar engine on the CPU backend.  For the wave engine
# on a TPU VM: base off a TPU-enabled JAX image, set MINISCHED_DEVICE_MODE=1
# and JAX_PLATFORMS=tpu,cpu (keep cpu in the list), run ONE container per
# chip (a chip belongs to one process), and mount a volume at
# JAX_COMPILATION_CACHE_DIR so restarts load their executables instead of
# compiling them.  The process logs the platform/device_kind it got;
# `python chip_smoke.py` is the check that the path starts on the chip.
FROM python:3.12-slim

RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ make \
    && rm -rf /var/lib/apt/lists/*

# jax (CPU) is the only hard runtime dependency of the scheduler process
RUN pip install --no-cache-dir "jax[cpu]" numpy

WORKDIR /app
COPY Makefile ./
COPY native ./native
COPY minisched_tpu ./minisched_tpu

# build the native host-table kernels into the package (Makefile `native`:
# the package's own digest-checked build, failing if it fell back to NumPy)
RUN make native

ENV PORT=10251 \
    FRONTEND_URL=http://localhost:3000 \
    MINISCHED_TPU_STORE_URL=file:///data/cluster.wal \
    JAX_PLATFORMS=cpu

EXPOSE 10251
VOLUME /data

# the standalone process entry (reference sched.go boot order: store →
# API server → PV controller → scheduler; SIGTERM-clean)
CMD ["python", "-m", "minisched_tpu"]
