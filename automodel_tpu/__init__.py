"""TPU AutoModel — a TPU-native (JAX/XLA/Pallas/pjit) training framework.

Brand-new implementation of the capabilities of NVIDIA-NeMo/Automodel
(see SURVEY.md): YAML-recipe-driven pretraining / SFT / PEFT / KD for LLMs,
MoE models, VLMs and retrieval models, loading Hugging Face checkpoints into
sharded device arrays. Parallelism is pure configuration over one named
device mesh (`pp / dp_replicate / dp_shard / ep / cp / tp`) via GSPMD
NamedSharding — the TPU-native analog of the reference's DTensor/FSDP2 stack
(reference: nemo_automodel/components/distributed/mesh.py:42).
"""

__version__ = "0.1.0"
