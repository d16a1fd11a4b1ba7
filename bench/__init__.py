"""The chip benchmark of the Div-DPP reranker (``python bench/run.py``)."""
