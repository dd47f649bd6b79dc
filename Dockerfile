# TPU-native TNN rebuild — container image (parity: the reference's
# Ubuntu 24.04 Dockerfile + docker-compose multi-node sims).
#
#   docker build -t tnn-tpu .
#   docker run --rm tnn-tpu python -m pytest tests/ -x -q          # CPU suite
#   docker run --rm --privileged tnn-tpu python chip_smoke.py      # on a TPU VM
#
# On Cloud TPU VMs pass through /dev/accel* and install the libtpu wheel that
# matches the runtime; on CPU the suite runs on a virtual 8-device mesh.
FROM ubuntu:24.04

RUN apt-get update && apt-get install -y --no-install-recommends \
        python3 python3-pip python3-venv g++ make zlib1g-dev git \
    && rm -rf /var/lib/apt/lists/*

RUN python3 -m venv /opt/venv
ENV PATH=/opt/venv/bin:$PATH

# JAX CPU by default; the TPU extra is selected at build time for TPU VMs:
#   docker build --build-arg JAX_EXTRA=tpu -t tnn-tpu .
ARG JAX_EXTRA=cpu
RUN pip install --no-cache-dir "jax[${JAX_EXTRA}]" flax optax orbax-checkpoint \
        chex einops numpy pytest pillow scikit-learn

WORKDIR /app
COPY . .
RUN pip install --no-cache-dir -e . && make -C native -j

# default: run the test suite on the virtual 8-device CPU mesh
ENV XLA_FLAGS=--xla_force_host_platform_device_count=8
CMD ["python", "-m", "pytest", "tests/", "-x", "-q"]
