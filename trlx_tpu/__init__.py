"""trlx_tpu — a TPU-native (JAX/XLA/pjit/Pallas) RLHF framework.

Capability-equivalent to trlx v0.2.0 (reference: /root/reference), redesigned
TPU-first: functional Flax models over a `jax.sharding.Mesh`, single pjit'd
train steps, `lax.scan`/`lax.while_loop` control flow, Pallas kernels for hot
ops, and XLA collectives (psum/all_gather/ppermute) over ICI/DCN instead of
NCCL/DeepSpeed.

Public API mirrors the reference's single entry point
(reference: trlx/__init__.py:1, trlx/trlx.py:13-93):

    import trlx_tpu
    trlx_tpu.train("gpt2", reward_fn=...)          # online PPO
    trlx_tpu.train("gpt2", dataset=(samples, rs))  # offline ILQL

The ``train`` export is lazy (PEP 562): bare ``import trlx_tpu`` must stay
jax-free so jax-less subsystems (``python -m trlx_tpu.analysis``, the
CPU-only `make lint` CI job) can import the package without the accelerator
stack.
"""

IMPORT_T0 = __import__("time").time()  # where `setup/import_s` counts from (utils/startup.py)

__version__ = "0.1.0"

__all__ = ["train", "__version__"]


def __getattr__(name):
    if name == "train":
        from trlx_tpu.trlx import train

        return train
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
