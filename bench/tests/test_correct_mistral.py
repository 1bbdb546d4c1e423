"""Every test of ``test_correct.py`` on the tiny mistral-shaped
configuration (``data/tiny-mistral.json``: 24 query heads over 2 KV heads,
no biases, untied embeddings, 4 layers), through the paged serving step's
split and padded rounds.  Its untied head gives logits near 1 where the
tiny qwen-shaped file's tied 0.02 embedding gives small ones, so bf16
rounding reads more: its limit (0.12) sits between the program's readings
(0.0032-0.0576) and the float8 control's (0.28-0.71) on seeds 1, 2, 3, 11,
2**31 + 3 and 2**31 + 12 here."""
import pytest

from test_correct import (  # noqa: F401  (collected here with this fixture)
    test_sound_run_is_correct_and_control_is_not,
    test_sound_runs_on_more_seeds,
    test_step_that_keeps_its_cache_is_not_correct,
    test_token_altered_where_produced_is_not_correct,
)


@pytest.fixture
def tiny(tiny):
    spec, cell = tiny["cell"]
    return dict(tiny, cell=(spec, dict(cell, config="tiny-mistral")))
