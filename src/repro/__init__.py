"""gLLM reproduction: globally-balanced pipeline-parallel LLM serving.
"""
