"""Traffic generators: ``traffic/<name>.py`` defines ``Traffic(job, vocab,
seed)`` with ``batch(step)``; a job names its generator by ``<name>``."""
