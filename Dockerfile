# horovod_tpu on a TPU host (reference Dockerfile + build-docker-images.sh,
# re-targeted: no CUDA/NCCL/OpenMPI layers — the TPU runtime is the libtpu
# wheel, the host runtime is the in-repo C++ core built at image build).
#
#   docker build -t horovod-tpu .
#   docker run --privileged --network host horovod-tpu \
#       python examples/jax_mnist.py
#
# --privileged + host networking are the standard TPU-VM container settings
# (device access via /dev/vfio, ICI/DCN via the host stack). One container
# per host; start `hvd-agent` in it for multi-host `hvdrun -H` jobs
# (docs/running.md).

FROM python:3.12-slim-bookworm

# Native toolchain for the C++ host runtime (cc/Makefile).
RUN apt-get update && apt-get install -y --no-install-recommends \
        g++ make \
    && rm -rf /var/lib/apt/lists/*

# The one installation the code is written for (horovod_tpu/compat.py and
# chip_smoke.py assume exactly these; PR 21 ran on them on a v5e).
RUN pip install --no-cache-dir "jax==0.9.0" "jaxlib==0.9.0" "libtpu==0.0.34" \
        -f https://storage.googleapis.com/jax-releases/libtpu_releases.html \
    && pip install --no-cache-dir "flax==0.12.3" "optax==0.2.6" \
        "orbax-checkpoint==0.11.32" "numpy==2.0.2" pytest

WORKDIR /opt/horovod_tpu
COPY . .

# Build the native core at image build (setup.py BuildWithNative), then
# install the package; the smoke test proves the ctypes bridge loads.
RUN pip install --no-cache-dir . \
    && python -c "import horovod_tpu as hvd; hvd.init(); \
                  assert hvd.size() >= 1; print('horovod_tpu ok')"

# Agent port for multi-host launches (hvdrun -H host1:8,host2:8).
EXPOSE 9009

CMD ["python", "-c", "import horovod_tpu as hvd; hvd.init(); print(hvd.rank(), hvd.size())"]
