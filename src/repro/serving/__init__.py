from repro.serving.topics import TopicRequest, TopicServer

__all__ = ["TopicRequest", "TopicServer"]
