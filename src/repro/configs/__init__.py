"""The paper's NMF corpus configurations (``repro.configs.nmf_paper``)."""
from repro.configs.nmf_paper import NMF_CONFIGS

__all__ = ["NMF_CONFIGS"]
