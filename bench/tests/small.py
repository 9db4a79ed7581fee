"""Small stand-ins of the cells, at sizes a CPU test run can hold."""

OVERRIDES = {
    "nemeth03.pd": {"config": {"n": 256}},
    "nemeth03.svd": {"config": {"n": 256}},
}
SECONDS = 1.0
SEED = 2**33 + 5
