# gubernator-tpu service container.
# For TPU nodes, base this on a jax[tpu] image instead; the code is
# identical (jax picks the TPU backend automatically).
FROM python:3.12-slim

RUN pip install --no-cache-dir "jax[cpu]" numpy grpcio protobuf \
    prometheus-client cryptography setuptools

WORKDIR /app
COPY gubernator_tpu/ gubernator_tpu/
COPY example.conf /etc/gubernator/gubernator.conf

# C++ fast lane (batch hashing + protobuf wire codec).  The image does
# not build without it: a container serving through the slow
# pure-Python lane would look like a slow device.
RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ \
    && python gubernator_tpu/ops/setup_native.py build_ext --inplace \
    && apt-get purge -y g++ && apt-get autoremove -y \
    && rm -rf /var/lib/apt/lists/*

ENV GUBER_GRPC_ADDRESS=0.0.0.0:1051 \
    GUBER_HTTP_ADDRESS=0.0.0.0:1050

EXPOSE 1050 1051 1052/udp
HEALTHCHECK --interval=15s --timeout=5s \
    CMD python -m gubernator_tpu.cmd.healthcheck \
        --url http://localhost:1050/v1/HealthCheck || exit 1

CMD ["python", "-m", "gubernator_tpu.cmd.daemon", \
     "--config", "/etc/gubernator/gubernator.conf"]
