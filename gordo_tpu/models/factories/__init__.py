from .feedforward_autoencoder import (
    feedforward_model,
    feedforward_symmetric,
    feedforward_hourglass,
)
from .lstm_autoencoder import lstm_model, lstm_symmetric, lstm_hourglass
from .transformer import transformer_model
from .hybrid import hybrid_moe_model
from .latent import latent_moe_model
from .sambay import sambay_model
from .tcn import tcn_model

__all__ = [
    "feedforward_model",
    "feedforward_symmetric",
    "feedforward_hourglass",
    "lstm_model",
    "lstm_symmetric",
    "lstm_hourglass",
    "transformer_model",
    "hybrid_moe_model",
    "latent_moe_model",
    "sambay_model",
    "tcn_model",
]
